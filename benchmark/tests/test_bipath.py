"""The undirected shortest-path deployment PR 46 brought (run:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
reference of the kind against a brute-force enumeration a vertex at a
time (random graphs, a hand graph with a pair stored in both orders,
the cut and its order); its control through the harness's own
comparison (the DIRECTED answer in its place, a both-orders pair read
as one step, the cut by the unsigned order: each ``correct: false`` by
``digest_mismatches`` and ``exact_mismatches`` and no other limit); the
kind found by name, the cell resolved and its files held to the path
cell's; one traced rehearsal through the harness; and the bytes a BFS
dispatch had to move, counted from the tables its levels read, with the
reader over one-sided and two-sided records and over a record from
before ``sides``.  CPU only: no number here is a device number."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (bfs_bytes, bfs_sides_bytes, bytes_model,  # noqa: E402
                       reference, run)
from benchmark.readers import (bfs_sides_roofline,  # noqa: E402
                               levels_roofline, setup_counter,
                               span_tag_ratio)
from benchmark.semantics import (shortest_path,  # noqa: E402
                                 shortest_path_bidirect as bipath)

BIPATH_CELL = "graph500-s20-bipath.bipath16"
PATH_CELL = "graph500-s20-path.closed16"
BIPATH_KIND = "shortest_path_bidirect"
BIPATH_CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
BIPATH_SEM = {"kind": BIPATH_KIND, "max_steps": 5, "max_paths": 1000,
              "edge": "knows"}


def _bipath_graph(edges) -> reference.Graph:
    src, dst = (np.asarray(c, np.int64) for c in zip(*edges))
    return reference.Graph(src, dst, [{"w": 0.0}],
                           np.zeros(len(src), np.int64))


def _bipath_edges(seed: int, n: int = 140, m: int = 300):
    """``m`` draws of an edge over ``n`` vertices, no self-loop, no
    pair twice in one order; some pairs in both."""
    rng = np.random.default_rng(seed)
    key = np.unique(rng.integers(1, n + 1, m) * (n + 1)
                    + rng.integers(1, n + 1, m))
    return [(int(k // (n + 1)), int(k % (n + 1))) for k in key
            if k // (n + 1) != k % (n + 1)]


def _bipath_brute(edges, a: int, b: int, max_steps: int, max_paths: int):
    """Every walk of 1 to ``max_steps`` steps from a, a step crossing a
    stored edge from either end, by plain enumeration over a dict a
    vertex at a time; the answer is the walks of the least length that
    end in b, cut by the stated order: read from the target backwards,
    a step (the vertex before, against before along)."""
    steps = {}
    for s, d in edges:
        steps.setdefault(s, []).append((d, True))       # along s -> d
        steps.setdefault(d, []).append((s, False))      # against it
    level = [((a,), ())]
    for _ in range(max_steps):
        level = [(vs + (v,), ways + (way,)) for vs, ways in level
                 for v, way in steps.get(vs[-1], ())]
        hits = [p for p in level if p[0][-1] == b]
        if hits and a != b:
            hits.sort(key=lambda p: [(p[0][i], p[1][i]) for i in
                                     range(len(p[1]) - 1, -1, -1)])
            return sorted(
                (str(vs[0]) + "".join(
                    f" <{'' if way else '-'}knows,0> {v}"
                    for v, way in zip(vs[1:], ways)),)
                for vs, ways in hits[:max_paths])
    return []


@pytest.mark.parametrize("seed", [5, 2_900_000_046, 4_111_111_111])
def test_bipath_reference_agrees_with_brute_force_on_random_graphs(seed):
    edges = _bipath_edges(seed)
    g = _bipath_graph(edges)
    both = len(set(edges) & {(d, s) for s, d in edges}) // 2
    rng = np.random.default_rng(seed)
    lengths, cut, against = set(), 0, 0
    for _ in range(150):
        a, b = (int(x) for x in rng.integers(1, 141, 2))
        whole = _bipath_brute(edges, a, b, 4, 1000)
        assert g.answer({**BIPATH_SEM, "max_steps": 4}, (a, b)) == whole, \
            (a, b)
        some = g.answer({**BIPATH_SEM, "max_steps": 4, "max_paths": 3},
                        (a, b))
        assert some == _bipath_brute(edges, a, b, 4, 3), (a, b)
        lengths.add(whole[0][0].count("<") if whole else 0)
        cut += len(whole) > 3
        against += sum(r[0].count("<-") for r in whole)
    assert both >= 1 and {0, 2, 3, 4} <= lengths and cut >= 10 and against


# P (1) and Q (2) stored in both orders, R (3) that only points (at Q),
# a diamond from 10 to 20 over 11..14 whose edges lie both ways, a chain
# 30 -> 31 -> ... -> 36, and 40, alone with 41
BIPATH_BUILT = [(1, 2), (2, 1), (3, 2)] \
    + [(10, m) for m in (11, 12, 13, 14)] + [(11, 10), (13, 10)] \
    + [(m, 20) for m in (11, 12, 13, 14)] + [(20, 11), (20, 12)] \
    + [(30 + i, 31 + i) for i in range(6)] + [(40, 41)]


@pytest.mark.parametrize("a, b, rows", [
    (1, 1, 0),          # a = b
    (1, 2, 2),          # both orders: two edges, two paths
    (1, 3, 2),          # the target has an out-edge and no in-edge
    (3, 1, 2),
    (36, 30, 0),        # six steps
    (35, 30, 1),        # exactly five, all against
    (30, 35, 1),        # ... all along
    (1, 40, 0),         # another component
    (10, 20, 9),        # 4 + 2 + 2 + 1
    (20, 10, 9),
    (99, 1, 0),         # no such vertex
])
def test_bipath_built_cases(a, b, rows):
    got = _bipath_graph(BIPATH_BUILT).answer(BIPATH_SEM, (a, b))
    assert got == _bipath_brute(BIPATH_BUILT, a, b, 5, 1000)
    assert len(got) == rows
    if (a, b) == (1, 2):
        assert got == [("1 <-knows,0> 2",), ("1 <knows,0> 2",)]
    if (a, b) == (35, 30):
        assert got == [(" <-knows,0> ".join(map(str, range(35, 29, -1))),)]


def test_bipath_cut_takes_the_step_against_its_edge_first():
    g = _bipath_graph(BIPATH_BUILT)
    got = g.answer({**BIPATH_SEM, "max_paths": 7}, (10, 20))
    assert got == _bipath_brute(BIPATH_BUILT, 10, 20, 5, 7)
    # read from 20 backwards: 11 before 12 before 13; into 20 from 11
    # against the stored 20 -> 11 first, then along 11 -> 20, and under
    # each the step from 10 against 11 -> 10 before the one along
    # 10 -> 11; the two that are cut come last
    assert got == sorted((r,) for r in (
        "10 <-knows,0> 11 <-knows,0> 20", "10 <knows,0> 11 <-knows,0> 20",
        "10 <-knows,0> 11 <knows,0> 20", "10 <knows,0> 11 <knows,0> 20",
        "10 <knows,0> 12 <-knows,0> 20", "10 <knows,0> 12 <knows,0> 20",
        "10 <-knows,0> 13 <knows,0> 20"))
    whole = g.answer(BIPATH_SEM, (10, 20))
    assert sorted(set(whole) - set(got)) == [
        ("10 <knows,0> 13 <knows,0> 20",), ("10 <knows,0> 14 <knows,0> 20",)]


def test_bipath_steps_are_made_once_a_graph_and_hold_both_ends():
    edges = _bipath_edges(9)
    g = _bipath_graph(edges)
    ptr, before, along = bipath.steps_into(g)
    assert bipath.steps_into(g)[1] is before        # built once a graph
    assert len(before) == 2 * len(edges) == ptr[-1]
    for v in range(1, 60):
        want = sorted([(s, True) for s, d in edges if d == v]
                      + [(d, False) for s, d in edges if s == v])
        assert list(zip(before[ptr[v]:ptr[v + 1]].tolist(),
                        along[ptr[v]:ptr[v + 1]].tolist())) == want
    # a long list of entries is marked, a short one sorted: the same set
    some = np.arange(1, 120)
    assert np.array_equal(
        bipath.reached(ptr, before, some),
        np.unique(bipath.neighbours(ptr, before, some)))
    assert len(bipath.neighbours(ptr, before, some)) * 16 >= len(ptr)
    assert np.array_equal(bipath.reached(ptr, before, some[:2]),
                          np.unique(bipath.neighbours(ptr, before,
                                                      some[:2])))


# ---------------------------------------------------------- the control
class _BipathMix:
    """What ``run.compare`` reads of a ``workload.Mix``."""
    classes = [{"semantics": {**BIPATH_SEM, "max_paths": 3},
                "traversal": True, "served_counter": "rt.path_device"}]

    def is_traversal(self, ci: int) -> bool:
        return True


def _bipath_compared(edges, weaken) -> tuple:
    """The harness's own comparison over one response a pair, each the
    reference's answer as ``weaken`` leaves it; returns (correct, the
    numbers compared, how many answers it changed)."""
    g = _bipath_graph(edges)
    sem = _BipathMix.classes[0]["semantics"]
    rng = np.random.default_rng(46)
    records, changed = [], 0
    for _ in range(120):
        key = tuple(int(x) for x in rng.integers(1, 141, 2))
        want = g.answer(sem, key)
        ans = weaken(g, sem, key, want)
        changed += sorted(ans) != sorted(want)
        records.append({"cls": 0, "key": key, "problem": None,
                        "digest": reference.digest(ans),
                        "rows": reference.n_rows(ans), "answer": ans,
                        "due": 0.0, "sent": 0.0, "done": 0.1})
    src, dst = (np.asarray(c, np.int64) for c in zip(*edges))
    ev = {"data": {"src": src, "dst": dst, "edge_prop_table": [{"w": 0.0}],
                   "edge_prop_idx": np.zeros(len(src), np.int64)},
          "mix": _BipathMix(), "records": records, "largest": None,
          "warm_records": [], "health": [], "deadline_s": 0.0,
          "counters": {"start": {"rt.path_device": 0},
                       "after": {"rt.path_device": len(records)}}}
    return run.compare(ev), ev["compared"], changed


def _unsigned_cut(g, sem, key, want):
    """The cut by the forward statement's order, blind to the sign: the
    paths by their vertices read from the target, the first three."""
    def verts(row):
        return [int(x) for x in row[0].replace("<-knows,0>", "").replace(
            "<knows,0>", "").split()][::-1]

    whole = g.answer({**sem, "max_paths": 1000}, key)
    return sorted(sorted(whole, key=lambda r: (verts(r), -r[0].count("<-")))
                  [:sem["max_paths"]])


BIPATH_CONTROLS = {
    "sound": lambda g, sem, key, want: list(want[::-1]),
    # one table read where two are asked: the forward statement's answer
    "directed": lambda g, sem, key, want: shortest_path.answer(g, sem, key),
    # a path taken as a sequence of vertices: the pair stored in both
    # orders is one step, printed along
    "one_step_a_pair": lambda g, sem, key, want: sorted(
        {(r[0].replace("<-knows", "<knows"),) for r in want}),
    # the cut by the unsigned order (+t before -t where both lie there)
    "unsigned_cut": _unsigned_cut,
}


@pytest.mark.parametrize("weaken", sorted(BIPATH_CONTROLS))
def test_bipath_control_is_not_correct(weaken, capsys):
    """The reference in the program's place with the fifth guarantee
    broken comes out ``correct: false``, by the digest and by the exact
    comparison and by no other limit; unbroken, the same drive is
    correct with every number at its limit."""
    edges = _bipath_edges(2_900_000_046)
    # half of the edges stored in both orders, so that the sign decides
    # a cut often
    edges = sorted(set(edges) | {(d, s) for s, d in edges[::2]})
    correct, compared, changed = _bipath_compared(
        edges, BIPATH_CONTROLS[weaken])
    capsys.readouterr()
    limits = {name: number["value"] for name, number in compared.items()}
    assert limits["served_counter_short"] == 0
    assert limits["health_problems"] == 0
    assert limits["responses"] == limits["answered"] == 120
    if weaken == "sound":
        assert correct is True and changed == 0
        assert limits["digest_mismatches"] == limits["exact_mismatches"] == 0
    else:
        assert correct is False and changed >= 5
        assert limits["digest_mismatches"] == changed
        assert limits["exact_mismatches"] == changed


# ----------------------------------------------------- the cell's files
def test_the_bipath_kind_is_found_by_name_and_its_cell_resolves():
    assert reference.semantics_module(BIPATH_KIND) is bipath
    assert bipath.ARITY == 2
    spec = run.load_json(ROOT, "BENCHMARK.json")
    parts = run.resolve(spec, BIPATH_CELL)
    closed16 = run.resolve(spec, PATH_CELL)
    assert parts["cell"] == {
        "name": BIPATH_CELL, "config": "graph500-s20-bipath",
        "traffic": "bipath16", "chips": 1, "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200
    assert len(spec["workloads"]) == 9 and len(spec["configs"]) == 7
    assert not [w for w in spec["workloads"] if w["chips"] != 1]
    # closed16's file but for the word and the kind (and the prose)
    traffic, theirs = parts["traffic"], closed16["traffic"]
    for key in ("groups", "start_keys", "warmup", "trace", "check",
                "selfcheck"):
        assert traffic[key] == theirs[key], key
    assert list(traffic["classes"]) == list(theirs["classes"]) == ["path"]
    cls, other = traffic["classes"]["path"], theirs["classes"]["path"]
    assert cls["template"] == ("FIND SHORTEST PATH FROM {v0} TO {v1} OVER "
                               "knows BIDIRECT UPTO 5 STEPS")
    assert cls["template"].replace(" BIDIRECT", "") == other["template"]
    assert cls["semantics"] == {**other["semantics"], "kind": BIPATH_KIND}
    assert cls["semantics"]["max_paths"] == 1000
    assert cls["served_counter"] == "rt.path_device" and cls["traversal"]
    assert traffic["groups"] == [{"loop": "closed", "clients": 16,
                                  "sequence": 16384,
                                  "shares": {"path": 1.0}}]
    assert traffic["who_sends_it"] and "IC13" in traffic["who_sends_it"]
    assert {m["name"] for m in parts["end_to_end"]} \
        == {"qps", "device_bytes_per_edge", "setup_s"}
    # graph500-s20-path's deployment edge for edge ...
    path, config = closed16["config"], parts["config"]
    for key in ("generator", "generator_params", "structure_seed",
                "partition_num", "replica_factor", "flags", "layout",
                "edge", "space", "selfcheck", "reduced"):
        assert config[key] == path[key], key
    # ... declared and not pinned: no set-up statement but CREATE EDGE
    assert config["schema"] == ["CREATE EDGE knows(w double)"]
    assert config["requires"] == {
        "flags": ["find_path_max_paths"],
        "statements": ["FIND SHORTEST PATH FROM 1 TO 2 OVER knows BIDIRECT "
                       "UPTO 5 STEPS"],
        "counters": ["rt.path_device"]}
    assert config["guarantees"][:3] == path["guarantees"][:3]
    assert len(config["guarantees"]) == 4
    for said in ("from either end", "sequence of EDGES", "<-knows,0>",
                 "385,611", "-knows before +knows"):
        assert said in config["guarantees"][-1], said
    assert "UNDIRECTED" in config["source"] and "IC13" in config["source"]
    assert len(config["source"]) <= 200
    assert config["reduced"] == ["scale"] and config["reduced_why"]["scale"]
    assert config["from_source"] and config["assumed"]
    entry = next(c for c in spec["configs"]
                 if c["name"] == "graph500-s20-bipath")
    assert entry == {"name": "graph500-s20-bipath",
                     "source": config["source"],
                     "file": "benchmark/configs/graph500-s20-bipath.json",
                     "reduced": ["scale"], "why": entry["why"]}
    assert len(entry["why"]) <= 200
    # every family closed16 lists, in closed16's order, but the roofline
    # that reckons one table a level; then the two this cell brings
    listed = [m["name"] for m in parts["per_layer"]]
    assert listed == [m["name"] for m in closed16["per_layer"]
                      if m["name"] not in ("bfs_roofline.qps",
                                           "bfs_sides_roofline.qps")] \
        + ["bfs_sides_roofline.qps", "path_rev_step_share.qps",
           "path_index_s"]
    for m in spec["per_layer"][-3:]:
        # one BFS roofline for both path cells (a one-signed record is
        # sides = 1 of the same count); the other two are this cell's
        assert m["workloads"] == [BIPATH_CELL] + (
            [PATH_CELL] if m["name"] == "bfs_sides_roofline.qps" else [])
        assert m["moves"] == ("setup_s" if m["name"] == "path_index_s"
                              else "qps")
    assert len(spec["per_layer"]) == 119 <= 128
    assert all("workloads" in m for m in spec["per_layer"])
    assert BIPATH_CELL in next(m for m in spec["end_to_end"]
                               if m["name"] == "qps")["workloads"]


def test_a_rehearsal_of_the_bipath_cell_is_correct_and_reads_both_tables(
        monkeypatch):
    """The cell through the harness as a chip run goes (the rehearsal's
    size, CPU jax), traced: every answer right and device-served, every
    dispatch two-sided, edges crossed against their direction, and the
    readers find what they read (those of the device trace have none
    here)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    parts = run.resolve(run.load_json(ROOT, "BENCHMARK.json"), BIPATH_CELL)
    out = run.run_cell(parts, seed=4_600_000_029, seconds=2.0,
                       trace=True, device=BIPATH_CPU, tiny=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 16
    for name, number in out["compared"].items():
        assert number["value"] == number.get("limit", number["value"]), \
            name
    assert out["compared"]["answered"]["value"] == out["attempted"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0.0 < metrics["path_rev_step_share.qps"] < 1.0
    assert metrics["path_rows_per_stmt.qps"] > 1
    assert 0.0 <= metrics["path_capped_share.qps"] <= 1.0
    assert 1.0 <= metrics["bfs_levels.qps"] <= 5.0
    assert 0.0 < metrics["bfs_swept_share.qps"] <= 1.0
    assert metrics["path_reconstruct_ms.qps"] > 0
    assert out["notes"]["compiles_in_window"] == 0
    assert out["notes"]["guards_off"] == {}
    assert set(out["notes"]["missing_per_layer"]) <= {
        m["name"] for m in parts["per_layer"]
        if m["source"] == "device_trace"}
    assert "bfs_sides_roofline.qps" in out["notes"]["missing_per_layer"]
    grown = out["notes"]["counter_growth"]
    assert grown["rt.path_device"] >= out["attempted"]
    assert grown.get("rt.hop_onesided", 0) == 0     # no level read one table
    assert grown.get("rt.path_index_builds", 0) == 0    # built in warm-up
    assert 0.0 < metrics["path_index_s"] < out["notes"]["stages"]["warmup"]


# ------------------------------------------- the bytes a dispatch moved
BFS_SHAPES = [[600, 8], [400, 512]]     # 209,600 slots a table, 1,000 rows
BFS_TABLE = 209_600
BFS_SIZES = (4, 1)                      # index, etype bytes
# at 128 lanes: 16 B a word row; a row's pass is two word rows and 128
# int16 depths read and written
BFS_ROWS_128 = 1_000 * (2 * 16 + 2 * 128 * 2)


@pytest.mark.parametrize("levels, pushed, slots, sides, moved", [
    # one pulled level of one table: the sweep at 21 B a slot, the rows
    (1, 0, BFS_TABLE, 1, BFS_TABLE * 21 + BFS_ROWS_128),
    # ... of both tables: the sweep twice, the rows once
    (1, 0, 2 * BFS_TABLE, 2, 2 * BFS_TABLE * 21 + BFS_ROWS_128),
    # two pushes (520 live slots at 37 B) and two two-sided pulls
    (4, 2, 4 * BFS_TABLE + 520, 2,
     4 * BFS_TABLE * 21 + 520 * 37 + 4 * BFS_ROWS_128),
    # pushes alone: the slots and the rows' pass a level
    (3, 3, 96, 2, 96 * 37 + 3 * BFS_ROWS_128),
    (0, 0, 0, 2, 0),
])
def test_a_bfs_dispatch_moves_what_its_levels_read(levels, pushed, slots,
                                                   sides, moved):
    assert bytes_model.table_slots(BFS_SHAPES) == BFS_TABLE
    assert bfs_sides_bytes.dispatch_bytes(
        levels, pushed, slots, sides, BFS_SHAPES, *BFS_SIZES, 128) == moved


def test_bfs_dispatch_bytes_against_the_one_table_model():
    # every level a one-sided pull: bfs_bytes.level_bytes a level, to
    # the byte, at either rung
    for lanes in (128, 256):
        assert bfs_sides_bytes.dispatch_bytes(
            3, 0, 3 * BFS_TABLE, 1, BFS_SHAPES, *BFS_SIZES, lanes) \
            == 3 * bfs_bytes.level_bytes(BFS_SHAPES, *BFS_SIZES, lanes)
    # read as one-sided, a two-sided record's second table lands among
    # the pushed slots at 37 B where a pull moves 21: too high
    two = bfs_sides_bytes.dispatch_bytes(
        1, 0, 2 * BFS_TABLE, 2, BFS_SHAPES, *BFS_SIZES, 128)
    blind = bfs_sides_bytes.dispatch_bytes(
        1, 0, 2 * BFS_TABLE, 1, BFS_SHAPES, *BFS_SIZES, 128)
    assert blind - two == BFS_TABLE * (37 - 21)
    # pulls that report fewer slots than the tables they swept hold,
    # more pushes than levels, a record that says nothing: no bytes
    for record in ((1, 0, BFS_TABLE, 2), (1, 2, BFS_TABLE, 1),
                   (2, 0, None, 2), (2, 0, 2 * BFS_TABLE, None),
                   (None, None, None, None)):
        assert bfs_sides_bytes.dispatch_bytes(
            *record, BFS_SHAPES, *BFS_SIZES, 128) is None


def _bipath_layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics", name + ".json")


def _bfs_record(flight, program_s=0.5, **over) -> dict:
    record = {"trace": {"program_s": {"jit_bfs": program_s, "jit_hop": 9.0},
                        "program_runs": {"jit_bfs": 2, "jit_hop": 3}},
              "traced_us": (1000.0, 2000.0),
              "peaks": {"hbm_bytes_per_s": 1e9},
              "facts": {"ell_shapes": BFS_SHAPES, "ell_index_itemsize": 4,
                        "ell_etype_itemsize": 1},
              "flight": flight}
    record.update(over)
    return record


def _bfs_dispatch(time_us, **fields) -> dict:
    return {"kind": "dispatch", "kernel": "ell_bfs", "rung": 128,
            "steps": 5, "queries": 9, "time_us": time_us, **fields}


def test_the_bfs_sides_roofline_on_two_sided_and_one_sided_records():
    layer = _bipath_layer("bfs_sides_roofline")
    assert layer["reader"] == "bfs_sides_roofline"
    assert layer["select"] == {
        "program": "^jit_bfs$", "kind": "dispatch", "kernel": "ell_bfs",
        "levels": "levels", "pushed": "levels_push", "slots": "slots",
        "sides": "sides", "lanes": "rung"}
    two_sided = [
        _bfs_dispatch(500.0, levels=4, levels_push=1, sides=2,
                      hop_onesided=0, slots=6 * BFS_TABLE + 9),
        _bfs_dispatch(1200.0, levels=3, levels_push=1, sides=2,
                      hop_onesided=0, slots=4 * BFS_TABLE + 48,
                      swept=350_000),
        _bfs_dispatch(1900.0, levels=2, levels_push=2, sides=2,
                      hop_onesided=0, slots=64, swept=64),
        _bfs_dispatch(2500.0, levels=5, levels_push=0, sides=2,
                      hop_onesided=0, slots=10 * BFS_TABLE),
        {"kind": "dispatch", "kernel": "ell_go", "rung": 128,
         "time_us": 1500.0},
        {"kind": "tick", "time_us": 1500.0, "levels": 40}]
    # the two records inside the interval: two two-sided pulls, three
    # pushes of 48 + 64 slots, five passes over the rows
    moved = 4 * BFS_TABLE * 21 + (48 + 64) * 37 + 5 * BFS_ROWS_128
    got = bfs_sides_roofline.read(layer["select"], _bfs_record(two_sided))
    assert got == pytest.approx(100.0 * moved / 1e9 / 0.5)
    # the share counts the table, not the reach: ``swept`` moves nothing
    for r in two_sided:
        r.pop("swept", None)
    assert bfs_sides_roofline.read(layer["select"],
                                   _bfs_record(two_sided)) == got
    # a forward statement's records, every level a pull: what
    # bfs_roofline.qps reads of them, to the digit
    one_sided = [_bfs_dispatch(1200.0, levels=5, levels_push=0, sides=1,
                               hop_onesided=5, slots=5 * BFS_TABLE),
                 _bfs_dispatch(1900.0, levels=3, levels_push=0, sides=1,
                               hop_onesided=3, slots=3 * BFS_TABLE)]
    old = levels_roofline.read(_bipath_layer("bfs_roofline")["select"],
                               _bfs_record(one_sided))
    assert bfs_sides_roofline.read(
        layer["select"], _bfs_record(one_sided)) == pytest.approx(old)
    # ... and with a level pushed, less: the old reader reckons the push
    # a sweep
    one_sided[0].update(levels_push=1, slots=4 * BFS_TABLE + 520)
    assert bfs_sides_roofline.read(
        layer["select"], _bfs_record(one_sided)) < old


@pytest.mark.parametrize("why", [
    "no_sides", "no_slots", "slots_belie_the_tables", "no_records",
    "no_trace", "no_peaks", "no_program"])
def test_the_bfs_sides_roofline_reads_nothing_where_there_is_nothing(why):
    """A program from before ``sides`` (PR 40's parent), a CPU
    rehearsal (no peaks, no trace), no jit_bfs in the trace, pulls that
    report one table for two: None each time (left out of the line,
    named on stderr), never 0, and no raise."""
    select = _bipath_layer("bfs_sides_roofline")["select"]
    rec = _bfs_record([_bfs_dispatch(1200.0, levels=2, levels_push=0,
                                     sides=2, slots=4 * BFS_TABLE)])
    assert bfs_sides_roofline.read(select, rec) is not None
    if why == "no_sides":
        del rec["flight"][0]["sides"]
    elif why == "no_slots":
        del rec["flight"][0]["slots"]
    elif why == "slots_belie_the_tables":
        rec["flight"][0]["slots"] = 2 * BFS_TABLE
    elif why == "no_records":
        rec["flight"][0]["time_us"] = 2500.0
    elif why == "no_trace":
        rec["trace"] = rec["traced_us"] = None
    elif why == "no_peaks":
        rec["peaks"] = None
    else:
        rec["trace"]["program_s"].pop("jit_bfs")
        rec["trace"]["program_runs"].pop("jit_bfs")
    assert bfs_sides_roofline.read(select, rec) is None


def test_the_cell_size_two_sided_bfs_cannot_pass_its_roofline():
    """At the cell's tables (2 x 24,835,040 slots, 657,674 rows) and the
    128-lane rung a two-sided pulled level has to move 1.40 GB, 1.71 ms
    at 819 GB/s, against the tens of ms a sweep of both tables takes."""
    shapes = [[452588, 8], [56666, 16], [74253, 32], [6223, 64],
              [34651, 128], [15422, 256], [17871, 512]]     # PR 39's tables
    moved = bfs_sides_bytes.dispatch_bytes(
        1, 0, 2 * 24_835_040, 2, shapes, 4, 1, 128)
    assert moved == 2 * 24_835_040 * 21 + 657_674 * (2 * 16 + 2 * 128 * 2)
    assert moved / 819e9 == pytest.approx(1.705e-3, rel=1e-2)


def test_the_rev_step_share_is_read_off_the_walk_s_tags():
    """Steps crossed against their direction over all steps of the
    returned paths; nothing where the walk writes no such tags (this
    PR's parent) or returned no path, never 0 for those."""
    layer = _bipath_layer("path_rev_step_share")
    assert layer["reader"] == "span_tag_ratio"

    def tree(tags):
        return {"roots": [{"name": "graph.query", "children": [
            {"name": "tpu.path_reconstruct", "tags": tags,
             "duration_us": 9}]}]}

    trees = [tree({"paths": 2, "steps": 6, "rev_steps": 3}),
             tree({"paths": 0, "steps": 0, "rev_steps": 0}),
             tree({"paths": 4, "steps": 14, "rev_steps": 7})]
    assert span_tag_ratio.read(layer["select"], {"trees": trees}) \
        == pytest.approx(0.5)
    assert span_tag_ratio.read(
        layer["select"], {"trees": [tree({"paths": 3})]}) is None
    assert span_tag_ratio.read(
        layer["select"],
        {"trees": [tree({"paths": 0, "steps": 0, "rev_steps": 0})]}) is None
    assert span_tag_ratio.read(layer["select"], {"trees": []}) is None


def test_the_index_clock_is_read_off_the_set_up_s_counter_growth():
    """What the predecessor order's build added to ``warmup_s``: the
    counter's growth from the loaded deployment to the window's start,
    in seconds; nothing where the program has no such counter (this
    PR's parent) or built no index in set-up, never 0."""
    layer = _bipath_layer("path_index_s")
    assert layer == {"reader": "setup_counter",
                     "select": {"counter": "rt.path_index_us",
                                "scale": 1e-06}}

    def record(start, before, after=None):
        return {"counters": {"start": start, "before": before,
                             "after": after or before}}

    assert setup_counter.read(layer["select"], record(
        {"rt.path_index_us": 0}, {"rt.path_index_us": 4_250_000},
        {"rt.path_index_us": 9_999_999})) == pytest.approx(4.25)
    assert setup_counter.read(layer["select"], record(
        {"rt.path_index_us": 7}, {"rt.path_index_us": 7})) is None
    assert setup_counter.read(layer["select"], record(
        {"rt.path_device": 0}, {"rt.path_device": 21})) is None
