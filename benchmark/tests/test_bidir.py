"""The undirected k-hop neighbourhood count deployment PR 40 brought
(run: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
reference of the kind against a brute-force walk a vertex at a time and
against ``go_count_distinct`` on an explicitly doubled edge list, both
sides of its hop forced; its control through the harness's own
comparison (the count off by one, and the DIRECTED count in its place:
each ``correct: false`` by ``digest_mismatches`` and
``exact_mismatches`` and no other limit); the kind found by name, the
cell resolved and its configuration held to ``graph500-s20-khop``'s;
one traced rehearsal through the harness; and the bytes model that
counts the tables a hop read (one module and one reader since PR 45:
``hop_roofline.*`` lists this cell too) on hand-made one-sided and
two-sided records.  CPU only: no number here is a device number."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_model, reference, run  # noqa: E402
from benchmark.readers import sides_roofline  # noqa: E402
from benchmark.semantics import (go_count_distinct,  # noqa: E402
                                 go_count_distinct_bidirect as bidir)

BIDIR_CELL = "graph500-s20-bidir.bicount16"
KHOP_CELL = "graph500-s20-khop.count16"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
KIND = "go_count_distinct_bidirect"


def _edges(seed: int, n: int = 300, m: int = 900,
           sources: float = 0.6):
    """``m`` draws of an edge over ``n`` vertices, the start of each
    among the first ``sources`` of them (the rest only receive), no
    self-loop, no pair twice in one order; some pairs in both."""
    rng = np.random.default_rng(seed)
    key = np.unique(rng.integers(0, int(n * sources), m) * n
                    + rng.integers(0, n, m))
    src, dst = key // n + 1, key % n + 1
    keep = src != dst
    return src[keep], dst[keep]


def _graph(src, dst) -> reference.Graph:
    return reference.Graph(src, dst, [{"w": 0.0}],
                           np.zeros(len(src), np.int64))


def _bidir_graph(seed: int, **kw) -> reference.Graph:
    return _graph(*_edges(seed, **kw))


def _brute(src, dst, start: int, steps: int) -> int:
    """A vertex at a time over Python sets and the raw edge list:
    nothing of the CSR, nothing of numpy's marking."""
    pairs = list(zip(src.tolist(), dst.tolist()))
    frontier = {start}
    for _ in range(steps):
        frontier = {d for s, d in pairs if s in frontier} \
            | {s for s, d in pairs if d in frontier}
    return len(frontier)


@pytest.mark.parametrize("steps", [1, 2, 3, 6])
@pytest.mark.parametrize("seed", [17, 4_000_000_040])
def test_bidir_reference_matches_a_brute_force_walk(seed, steps):
    src, dst = _edges(seed, m=420)      # sparse: walks that end early
    g = _graph(src, dst)
    # the same walk, directed, over the edge list written out both ways
    doubled = _graph(np.concatenate((src, dst)),
                     np.concatenate((dst, src)))
    both_ways = len(set(zip(src.tolist(), dst.tolist()))
                    & set(zip(dst.tolist(), src.tolist())))
    assert both_ways            # a pair stored twice is one neighbour
    sem = {"kind": KIND, "steps": steps}
    some = none = receivers = 0
    for start in range(1, 80):
        n = _brute(src, dst, start, steps)
        assert g.answer(sem, start) == ([(n,)] if n else [])
        for side in (False, True):          # the hop from either side
            assert bidir.khop_count(g, start, steps, pull=side) == n
            assert go_count_distinct.khop_count(
                doubled, start, steps, pull=side) == n
        some += n > 0
        none += n == 0
        # a vertex that only receives has no directed walk and has an
        # undirected one
        receivers += n > 0 and not g.deg[start]
    assert some > 40 and none and receivers


def test_the_symmetrised_graph_holds_every_edge_at_both_ends():
    src, dst = _edges(23)
    g = _graph(src, dst)
    both = g.reading(undirected=True)
    ptr, nbr, deg = both.ptr, both.adj, both.deg
    assert both.asked[0] is ptr and both.asked[1] is nbr   # one CSR
    assert len(nbr) == 2 * len(src) and ptr[-1] == len(nbr)
    assert np.array_equal(deg, np.bincount(
        np.concatenate((src, dst)), minlength=len(g.deg)))
    for v in range(1, 60):
        want = sorted(dst[src == v].tolist() + src[dst == v].tolist())
        row = nbr[ptr[v]:ptr[v + 1]]
        assert sorted(row.tolist()) == want
        assert np.all(np.diff(deg[row]) <= 0)   # the largest degree first
    assert g.reading(undirected=True) is both       # built once a graph
    small = np.asarray([3], np.int64)
    large = np.nonzero(deg > 0)[0][5:]
    for frontier in (small, large):
        seen = np.zeros(len(deg), bool)
        for v in frontier:
            seen[nbr[ptr[v]:ptr[v + 1]]] = True
        for pull in (None, False, True):
            assert np.array_equal(g.hop(frontier, True, pull),
                                  np.nonzero(seen)[0])
    assert int(deg[small].sum()) <= reference.PULL_FROM * len(nbr) \
        < int(deg[large].sum())


class _Mix:
    """What ``run.compare`` reads of a ``workload.Mix``."""

    def __init__(self, steps):
        self.classes = [{"semantics": {"kind": KIND, "steps": k},
                         "traversal": True,
                         "served_counter": "rt.go_device"}
                        for k in steps]

    def is_traversal(self, ci: int) -> bool:
        return True


def _compared(src, dst, weaken) -> tuple:
    """The harness's own comparison over one response a (class, key),
    each the reference's answer as ``weaken`` leaves it; returns
    (correct, the numbers compared, how many answers it changed)."""
    g, steps = _graph(src, dst), (2, 3)
    records, changed = [], 0
    for ci, k in enumerate(steps):
        for key in range(1, 60):
            want = g.answer({"kind": KIND, "steps": k}, key)
            ans = weaken(g, k, key, want)
            changed += ans != want
            records.append({"cls": ci, "key": key, "problem": None,
                            "digest": reference.digest(ans),
                            "rows": reference.n_rows(ans), "answer": ans,
                            "due": 0.0, "sent": 0.0, "done": 0.1})
    ev = {"data": {"src": src, "dst": dst, "edge_prop_table": [{"w": 0.0}],
                   "edge_prop_idx": np.zeros(len(src), np.int64)},
          "mix": _Mix(steps), "records": records, "largest": None,
          "warm_records": [], "health": [], "deadline_s": 0.0,
          "counters": {"start": {"rt.go_device": 0},
                       "after": {"rt.go_device": len(records)}}}
    return run.compare(ev), ev["compared"], changed


CONTROLS = {
    "sound": lambda g, k, key, want: list(want),
    # a vertex counted twice: a hub's extra row, a pad row, a vertex
    # reached over both tables and not ORed into one bit
    "off_by_one": lambda g, k, key, want:
        [(want[0][0] + 1,)] if want else [(1,)],
    # one table read where two are asked: the directed count
    "directed": lambda g, k, key, want:
        go_count_distinct.answer(g, {"steps": k}, key),
}


@pytest.mark.parametrize("seed", [17, 2_345_678_940, 4_000_000_040])
@pytest.mark.parametrize("weaken", sorted(CONTROLS))
def test_bidir_control_is_not_correct(seed, weaken, capsys):
    """The reference in the program's place with the fourth guarantee
    broken comes out ``correct: false``, by the digest and by the exact
    comparison and by no other limit; unbroken, the same drive is
    correct with every number at its limit."""
    src, dst = _edges(seed)
    correct, compared, changed = _compared(src, dst, CONTROLS[weaken])
    capsys.readouterr()
    limits = {name: number["value"] for name, number in compared.items()}
    assert limits["served_counter_short"] == 0
    assert limits["health_problems"] == 0
    assert limits["responses"] == 118
    if weaken == "sound":
        assert correct is True and changed == 0
        assert limits["digest_mismatches"] == 0
        assert limits["exact_mismatches"] == 0
    else:
        assert correct is False and changed > 40
        assert limits["digest_mismatches"] == changed
        assert limits["exact_mismatches"] == changed


def test_the_bidir_kind_is_found_by_name_and_its_cell_resolves():
    assert reference.semantics_module(KIND) is bidir
    spec = run.load_json(ROOT, "BENCHMARK.json")
    parts = run.resolve(spec, BIDIR_CELL)
    count16 = run.resolve(spec, KHOP_CELL)
    assert parts["cell"] == {
        "name": BIDIR_CELL, "config": "graph500-s20-bidir",
        "traffic": "bicount16", "chips": 1, "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200
    assert spec["workloads"][-1] == parts["cell"]
    # count16's file but for the word (and the prose about it)
    traffic, theirs = parts["traffic"], count16["traffic"]
    for key in ("groups", "start_keys", "warmup", "trace", "check",
                "selfcheck"):
        assert traffic[key] == theirs[key], key
    assert list(traffic["classes"]) == list(theirs["classes"])
    for name, cls in traffic["classes"].items():
        other = theirs["classes"][name]
        k = cls["semantics"]["steps"]
        assert cls["semantics"] == {"kind": KIND, "steps": k}
        assert other["semantics"] == {"kind": "go_count_distinct",
                                      "steps": k}
        assert cls["template"] == (
            f"GO {k} STEPS FROM {{v}} OVER knows BIDIRECT YIELD DISTINCT "
            f"knows._dst | YIELD COUNT(*)")
        assert cls["template"].replace(" BIDIRECT", "") \
            == other["template"]
        assert {a: b for a, b in cls.items()
                if a not in ("template", "semantics")} \
            == {a: b for a, b in other.items()
                if a not in ("template", "semantics")}
    assert [c["semantics"]["steps"]
            for c in traffic["classes"].values()] == [2, 3, 6]
    assert {m["name"] for m in parts["end_to_end"]} \
        == {"qps", "device_bytes_per_edge", "setup_s"}
    # graph500-s20-khop's deployment edge for edge ...
    khop = count16["config"]
    config = parts["config"]
    for key in ("generator", "generator_params", "structure_seed",
                "partition_num", "replica_factor", "flags", "layout",
                "edge", "space", "selfcheck", "reduced"):
        assert config[key] == khop[key], key
    # ... its schema and its tier's flag, and then the statement that
    # a program whose grammar lacks the word refuses: declared under
    # ``requires`` (PR 45), no set-up statement but CREATE EDGE
    assert config["schema"] == khop["schema"] == [
        "CREATE EDGE knows(w double)"]
    assert config["requires"] == {
        **khop["requires"],
        "statements": ["GO FROM 1 OVER knows BIDIRECT"]}
    assert config["guarantees"][:3] == khop["guarantees"][:3]
    assert len(config["guarantees"]) == 4
    assert "from either end" in config["guarantees"][-1]
    assert "UNDIRECTED" in config["source"]
    assert len(config["source"]) <= 200
    assert config["reduced"] == ["scale"] and config["reduced_why"]["scale"]
    assert config["from_source"] and config["assumed"]
    entry = spec["configs"][-1]
    assert entry == {"name": "graph500-s20-bidir",
                     "source": config["source"],
                     "file": "benchmark/configs/graph500-s20-bidir.json",
                     "reduced": ["scale"], "why": entry["why"]}
    # every .qps family count16 lists, in count16's order, and nothing
    # else: since PR 45 the roofline that counts the tables a hop read
    # is hop_roofline.qps itself
    listed = [m["name"] for m in parts["per_layer"]]
    assert listed == [m["name"] for m in count16["per_layer"]]
    assert "pump_unpack_cpu_share.qps" not in listed    # nothing unpacked
    for name in ("khop_count_roofline.qps", "hop_roofline.qps",
                 "hop_swept_share.qps", "hop_kernel_ms.qps",
                 "hop_sparse_share.qps", "tick_ms.qps",
                 "device_idle_pct.qps", "seat_hop_share.qps",
                 "pump_hold_ms.qps", "held_join_share.qps",
                 "fetch_bytes_per_stmt.qps", "pump_join_ms.qps",
                 "pump_hop_enqueue_ms.qps", "gil_late_ms.qps",
                 "gen_s", "load_s", "fold_s",
                 "ell_s", "compile_s", "warmup_s", "parse_us.qps"):
        assert name in listed, name
    assert "no hop_roofline.qps" not in parts["cell"]["why"]
    # the contract's size; the room PR 45 made below it is for PRs
    # that only add, and is held by no test
    assert len(spec["per_layer"]) <= 128
    assert not [m for m in spec["per_layer"]
                if m["name"].startswith("hop_sides_roofline")]
    assert BIDIR_CELL in next(m for m in spec["end_to_end"]
                              if m["name"] == "qps")["workloads"]


def test_a_rehearsal_of_the_cell_is_correct_and_reads_both_tables(
        monkeypatch):
    """The cell through the harness as a chip run goes (the
    rehearsal's size, CPU jax), traced: every answer right, every
    leaver counted on the device, every hop two-sided, and the readers
    find what they read (those of the device trace have none here)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    parts = run.resolve(run.load_json(ROOT, "BENCHMARK.json"), BIDIR_CELL)
    out = run.run_cell(parts, seed=4_000_000_029, seconds=2.0,
                       trace=True, device=CPU, tiny=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 16
    for name, number in out["compared"].items():
        assert number["value"] == number.get("limit", number["value"]), \
            name
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # what the retired guards watched is on the notes line; that no
    # hop was one-sided is the statement's text (BIDIRECT) and the
    # tick records' (the two-table bytes below read it)
    assert out["notes"]["khop_counted_share"] == 1.0
    assert "neigh_ridden_share" not in out["notes"]     # rows16's guard
    assert out["notes"]["guards_off"] == {}
    assert out["notes"]["counter_growth"].get("rt.hop_onesided", 0) == 0
    assert 0.5 < metrics["seat_hop_share.qps"] <= 1.0
    assert 3.0 < metrics["khop_hops_per_stmt.qps"] < 4.4   # (2+3+6)/3
    assert metrics["khop_vertices_per_stmt.qps"] > 1
    assert metrics["fetch_bytes_per_stmt.qps"] <= 4 * 128
    assert 0.0 < metrics["hop_swept_share.qps"] <= 1.0
    assert out["notes"]["compiles_in_window"] == 0
    assert set(out["notes"]["missing_per_layer"]) <= {
        m["name"] for m in parts["per_layer"]
        if m["source"] == "device_trace"}
    assert "hop_roofline.qps" in out["notes"]["missing_per_layer"]
    grown = out["notes"]["counter_growth"]
    assert grown["rt.go_count_distinct"] == grown["rt.go_device"] \
        == grown["rt.go_reduced"] == grown["rt.go_bidirect"]


# ------------------------------------------------ the two-table bytes
SHAPES = [[600, 8], [400, 512]]         # 209,600 slots a table, 1,000 rows
SIZES = (4, 1, 16)                      # index, etype, lane bytes (128 lanes)
TABLE = 209_600
CARRIERS = 1_000 * 4 * 16


@pytest.mark.parametrize("hops,pushes,slots,moved", [
    # what the one-table model read until PR 45, to the byte: a pull is
    # the table at 21 B a slot and the carriers, a push 37 B a slot
    (1, 0, TABLE, TABLE * 21 + CARRIERS),
    (3, 1, 2 * TABLE + 520, 2 * (TABLE * 21 + CARRIERS) + 520 * 37),
    (2, 2, 1_040, 1_040 * 37),
    (0, 0, 0, 0),
])
def test_a_one_sided_record_moves_what_one_table_holds(hops, pushes,
                                                       slots, moved):
    assert bytes_model.table_slots(SHAPES) == TABLE
    for onesided in (hops, None):   # None: a program before the field
        assert bytes_model.visited_bytes(
            hops, pushes, slots, onesided, SHAPES, *SIZES) == moved
    assert bytes_model.sides_of(3, 3) == 1 and bytes_model.sides_of(3, 0) == 2
    assert bytes_model.sides_of(0, 0) == 1      # no hop read no table


def test_a_two_sided_pull_is_the_pull_twice_less_one_carrier_term():
    one = bytes_model.pull_bytes(SHAPES, 1, *SIZES)
    assert one == TABLE * 21 + CARRIERS
    two = bytes_model.pull_bytes(SHAPES, 2, *SIZES)
    assert two == 2 * one - CARRIERS == 2 * TABLE * 21 + CARRIERS
    # a record of one two-sided pull reports both tables' slots
    assert bytes_model.visited_bytes(1, 0, 2 * TABLE, 0, SHAPES, *SIZES) \
        == two
    # two pulls and a push that visited 96 slots (3 rows of width 16,
    # both tables)
    assert bytes_model.visited_bytes(3, 1, 4 * TABLE + 96, 0, SHAPES,
                                     *SIZES) \
        == 2 * two + bytes_model.push_bytes(96, *SIZES)
    # the same record read as one-sided (what hop_roofline.qps would
    # have read here until PR 45): the second table lands among the
    # pushed slots, at 37 B where a pull moves 21
    wrong = bytes_model.visited_bytes(1, 0, 2 * TABLE, 1, SHAPES, *SIZES)
    assert wrong == one + TABLE * 37 and wrong > two
    # pulls that report fewer slots than the tables they swept hold,
    # more pushes than hops, a record that says nothing: no bytes
    assert bytes_model.visited_bytes(1, 0, TABLE, 0, SHAPES, *SIZES) is None
    assert bytes_model.visited_bytes(1, 2, TABLE, 0, SHAPES, *SIZES) is None
    assert bytes_model.visited_bytes(None, None, None, None, SHAPES,
                                     *SIZES) is None


def _layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         name + ".json")


def _record(ticks, **over) -> dict:
    kernel = {"name": "tpu.kernel", "start_us": 10, "duration_us": 5,
              "tags": {"kind": "ell_go_hop", "width": 128, "sides": 2},
              "children": []}
    record = {
        "trees": [{"roots": [{"name": "graph.query", "start_us": 0,
                              "duration_us": 100, "tags": {},
                              "children": [kernel]}]}],
        "flight": [{"kind": "tick", "time_us": 1e6 + i, **t}
                   for i, t in enumerate(ticks)]
        + [{"kind": "beat", "time_us": 1e6, "n": 100},
           {"kind": "tick", "time_us": 9e6, "hop_reads": 1,
            "hop_sparse": 0, "hop_slots": 419_200, "hop_onesided": 0}],
        "trace": {"program_s": {"jit_hop": 0.004, "jit_count": 0.001},
                  "program_runs": {"jit_hop": 3, "jit_count": 3}},
        "traced_us": (0.0, 5e6),
        "facts": {"ell_shapes": SHAPES, "ell_index_itemsize": 4,
                  "ell_etype_itemsize": 1, "ell_hub_rows": 24},
        "peaks": {"hbm_bytes_per_s": 819e9}}
    record.update(over)
    return record


def test_the_hop_roofline_on_hand_made_two_sided_and_one_sided_records():
    layer = _layer("hop_roofline")
    assert layer["reader"] == "sides_roofline"
    assert layer["select"]["onesided"] == "hop_onesided"
    two_sided = [{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 2 * TABLE,
                  "hop_onesided": 0, "hop_swept": 380_000},
                 {"hop_reads": 2, "hop_sparse": 1,
                  "hop_slots": 2 * TABLE + 96, "hop_onesided": 0,
                  "hop_swept": 380_096},
                 {"hop_reads": 0, "hop_sparse": 0, "hop_slots": 0,
                  "hop_onesided": 0, "hop_swept": 0}]
    pull = 2 * TABLE * 21 + CARRIERS
    moved = 2 * pull + 96 * 37      # the tick outside the interval: none
    got = sides_roofline.read(layer["select"], _record(two_sided))
    assert got == pytest.approx(100 * moved / 819e9 / 0.004)
    # a reader that does not ask the record for its sides (the select
    # of hop_roofline.* until PR 45) reads the same records 1.4 x too
    # high: the second table at a push's rate
    blind = {**layer["select"], "onesided": "no_such_field"}
    assert sides_roofline.read(blind, _record(two_sided)) > 1.3 * got
    # ... and one-sided records alike, with the field or without it
    one_sided = [{"hop_reads": 2, "hop_sparse": 1,
                  "hop_slots": TABLE + 48, "hop_onesided": 2}]
    want = 100 * (TABLE * 21 + CARRIERS + 48 * 37) / 819e9 / 0.004
    assert sides_roofline.read(layer["select"], _record(one_sided)) \
        == pytest.approx(want)
    assert sides_roofline.read(blind, _record(one_sided)) \
        == pytest.approx(want)
    # the share counts the table, not the reach: hop_swept moves nothing
    for t in two_sided:
        t["hop_swept"] = t["hop_slots"]
    assert sides_roofline.read(layer["select"], _record(two_sided)) == got


def test_the_hop_roofline_reads_nothing_where_there_is_nothing():
    """A CPU rehearsal (no peaks), no trace, no jit_hop in the trace,
    no kernel span, a program whose ticks carry no hop fields (before
    PR 28), pulls that report one table for two: None each time (left
    out of the line, named on stderr), and no raise."""
    select = _layer("hop_roofline")["select"]
    tick = [{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 419_200,
             "hop_onesided": 0}]
    assert sides_roofline.read(select, _record(tick)) is not None
    for over in ({"peaks": None}, {"trace": None}, {"traced_us": None},
                 {"trace": {"program_s": {"jit_count": 0.001},
                            "program_runs": {"jit_count": 3}}},
                 {"trees": []}):
        assert sides_roofline.read(select, _record(tick, **over)) is None
    assert sides_roofline.read(select, _record([{"leaves": 1}])) is None
    assert sides_roofline.read(select, _record(
        [{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 209_600,
          "hop_onesided": 0}])) is None
    assert sides_roofline.read(select, _record([])) is None


def test_the_cell_size_two_sided_pull_cannot_pass_its_roofline():
    """At the cell's tables (2 x 24,835,040 slots, 657,674 rows, 128
    lanes) a two-sided pull has to move 1.085 GB: 1.32 ms at 819 GB/s,
    against the ~0.13 s a pull of both tables is expected to take."""
    shapes = [[452588, 8], [56666, 16], [74253, 32], [6223, 64],
              [34651, 128], [15422, 256], [17871, 512]]     # PR 39's tables
    assert bytes_model.table_slots(shapes) == 24_835_040
    moved = bytes_model.pull_bytes(shapes, 2, 4, 1, 16)
    assert moved == 2 * 24_835_040 * 21 + 657_674 * 64
    assert moved / 819e9 == pytest.approx(1.325e-3, rel=1e-3)
