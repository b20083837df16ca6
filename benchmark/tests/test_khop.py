"""The k-hop neighbourhood count deployment PR 33 brought (run:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
reference of the kind against a brute-force walk, both sides of its
hop, its control (the count off by one, and the ``go_count`` kind's
edge count in its place: each ``correct: false``), the kind found by
name and the cell resolved, one rehearsal through the harness, and the
new per-layer readers on hand-made records.  CPU only: no number here
is a device number."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import count_bytes, reference, run  # noqa: E402
from benchmark.readers import (count_roofline, counter_delta,  # noqa: E402
                               flight_ratio, trace_program)
from benchmark.semantics import go_count, go_count_distinct  # noqa: E402

KHOP_CELL = "graph500-s20-khop.count16"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _khop_graph(seed: int, n: int = 300, m: int = 1500,
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


def _brute(g: reference.Graph, start: int, steps: int) -> int:
    """Edge by edge over Python sets: nothing of numpy's marking."""
    frontier = {start}
    for _ in range(steps):
        frontier = {int(g.dst[e]) for v in frontier
                    for e in range(g.ptr[v], g.ptr[v + 1])}
    return len(frontier)


@pytest.mark.parametrize("steps", [1, 2, 3, 6])
@pytest.mark.parametrize("seed", [11, 3_999_999_999])
def test_khop_reference_matches_a_brute_force_walk(seed, steps):
    g = _khop_graph(seed, m=600, sources=0.6)    # walks that end early
    sem = {"kind": "go_count_distinct", "steps": steps}
    some = none = 0
    for start in range(1, 60):
        n = _brute(g, start, steps)
        assert g.answer(sem, start) == ([(n,)] if n else [])
        assert n == len(g.frontier(start, steps))
        # the hop from either side gives the same set
        for pull in (False, True):
            assert go_count_distinct.khop_count(
                g, start, steps, pull) == n
        some += n > 0
        none += n == 0
    assert some > 20 and (none or steps == 1)


def test_the_hop_takes_the_cheaper_side():
    """Out of a set that holds a tenth of the edges or more the hop is
    pulled, out of a small one pushed: either way the walk's own set
    (test_hop.py holds the two sides to a walk that uses neither)."""
    g = _khop_graph(7, n=200, m=6000)
    small = np.asarray([3], np.int64)
    large = np.nonzero(g.deg > 0)[0][5:]
    for frontier, side in ((small, "push"), (large, "pull")):
        seen = np.zeros(len(g.deg), bool)   # the edges' own index
        seen[g.dst[g.edge_positions(frontier)]] = True
        for pull in (None, False, True):
            tally = {}
            assert np.array_equal(g.hop(frontier, pull=pull, tally=tally),
                                  np.nonzero(seen)[0])
            assert list(tally) == [
                side if pull is None else ("push", "pull")[pull]]
    assert int(g.deg[small].sum()) <= reference.PULL_FROM * len(g.dst) \
        < int(g.deg[large].sum())


@pytest.mark.parametrize("seed", [11, 2_345_678_901, 3_999_999_999])
@pytest.mark.parametrize("weaken", ["off_by_one", "edges_counted"])
def test_khop_control_is_not_correct(seed, weaken):
    """The reference in the program's place with the sixth guarantee
    broken: a vertex counted twice (a hub's extra row, a padding row),
    or the last hop's edges counted where its distinct end points are
    asked (what the COUNT(*) pushdown of a GO without DISTINCT
    returns): the digest and the exact comparison both say so."""
    g = _khop_graph(seed)
    judged = 0
    for steps in (2, 3):
        sem = {"kind": "go_count_distinct", "steps": steps}
        for start in range(1, 40):
            want = g.answer(sem, start)
            if not want:
                continue
            assert reference.digest(list(want)) == reference.digest(want)
            if weaken == "off_by_one":
                bad = [(want[0][0] + 1,)]
            else:
                bad = go_count.answer(g, {"steps": steps}, start)
                if bad == want:     # no vertex reached twice: a tree
                    continue
            assert reference.digest(bad) != reference.digest(want)
            assert not reference.same_rows(bad, want)
            judged += 1
    assert judged > 20


def test_the_khop_kind_is_found_by_name_and_its_cell_resolves():
    assert reference.semantics_module("go_count_distinct") \
        is go_count_distinct
    spec = run.load_json(ROOT, "BENCHMARK.json")
    parts = run.resolve(spec, KHOP_CELL)
    assert parts["cell"]["chips"] == 1
    classes = parts["traffic"]["classes"]
    assert [(c["semantics"]["kind"], c["semantics"]["steps"])
            for c in classes.values()] \
        == [("go_count_distinct", k) for k in (2, 3, 6)]
    for c in classes.values():          # the statement says the same
        assert c["template"] == (
            f"GO {c['semantics']['steps']} STEPS FROM {{v}} OVER knows "
            f"YIELD DISTINCT knows._dst | YIELD COUNT(*)")
        assert c["served_counter"] == "rt.go_device"
    group, = parts["traffic"]["groups"]
    assert (group["loop"], group["clients"], group["sequence"]) \
        == ("closed", 16, 16384)
    assert len(set(group["shares"].values())) == 1
    assert parts["traffic"]["check"]["keep_share"] == 1.0
    assert {m["name"] for m in parts["end_to_end"]} \
        == {"qps", "device_bytes_per_edge", "setup_s"}
    # the same graph as graph500-s20, edge for edge
    base = run.load_json(ROOT, "benchmark", "configs", "graph500-s20.json")
    config = parts["config"]
    for key in ("generator", "generator_params", "structure_seed",
                "partition_num", "replica_factor", "flags", "layout",
                "edge"):
        assert config[key] == base[key], key
    # ... and the tier the cell measures declared, not pinned: the
    # flag is required of the program and nothing is set (PR 45)
    assert config["schema"] == base["schema"] == [
        "CREATE EDGE knows(w double)"]
    assert config["requires"] == {"flags": ["go_dispatch_mode"]}
    assert "requires" not in base
    assert config["guarantees"][:3] == base["guarantees"]
    assert "exact number of distinct vertices" in config["guarantees"][-1]
    assert config["reduced"] == ["scale"]
    new = [m["name"] for m in parts["per_layer"]
           if m["name"].startswith("khop_")]
    assert new == ["khop_count_kernel_ms.qps", "khop_count_roofline.qps",
                   "khop_hops_per_stmt.qps", "khop_vertices_per_stmt.qps"]


def test_a_rehearsal_of_the_cell_is_correct_and_counts_on_the_device(
        monkeypatch):
    """The cell through the harness as a chip run goes (the
    rehearsal's size, CPU jax), traced: every answer right, every
    leaver counted, and the readers of what PR 33 added to the program
    find it (the two that read the device trace have none here)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    parts = run.resolve(run.load_json(ROOT, "BENCHMARK.json"), KHOP_CELL)
    out = run.run_cell(parts, seed=3_300_000_029, seconds=2.0,
                       trace=True, device=CPU, tiny=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 16
    for name, number in out["compared"].items():
        assert number["value"] == number.get("limit", number["value"]), \
            name
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # the retired guard is a key of the notes line, never compared
    assert out["notes"]["khop_counted_share"] == 1.0
    assert "khop_counted_share" not in out["compared"]
    assert out["notes"]["guards_off"] == {}
    assert 3.0 < metrics["khop_hops_per_stmt.qps"] < 4.4   # (2+3+6)/3
    assert metrics["khop_vertices_per_stmt.qps"] > 1
    assert metrics["fetch_bytes_per_stmt.qps"] <= 4 * 128
    assert set(out["notes"]["missing_per_layer"]) <= {
        m["name"] for m in parts["per_layer"]
        if m["source"] == "device_trace"}
    grown = out["notes"]["counter_growth"]
    assert grown["rt.go_count_distinct"] == grown["rt.go_device"] \
        == grown["rt.go_reduced"]


def _layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         name + ".json")


def _note(name: str) -> dict:
    """A guard of the notes line (``harness.json`` "notes")."""
    return run.load_json(ROOT, "benchmark", "harness.json")["notes"][name]


def _record(**over) -> dict:
    kernel = {"name": "tpu.kernel", "start_us": 10, "duration_us": 5,
              "tags": {"kind": "ell_lane_count", "width": 128},
              "children": []}
    record = {
        "trees": [{"roots": [{"name": "graph.query", "start_us": 0,
                              "duration_us": 100, "tags": {},
                              "children": [kernel]}]}],
        "flight": [{"kind": "tick", "leaves": 3, "handed": 0,
                    "counted": 3},
                   {"kind": "tick", "leaves": 1, "handed": 0,
                    "counted": 1},
                   {"kind": "tick", "leaves": 0, "handed": 0,
                    "counted": 0}],
        "counters": {
            "before": {"rt.go_count_distinct": 10,
                       "rt.count_distinct_hops": 40,
                       "rt.count_distinct_vertices": 1_000},
            "after": {"rt.go_count_distinct": 16,
                      "rt.count_distinct_hops": 62,
                      "rt.count_distinct_vertices": 601_000}},
        "statements_done": 6,
        "trace": {"program_s": {"jit_count": 0.002, "jit_hop": 1.5},
                  "program_runs": {"jit_count": 40, "jit_hop": 44}},
        "traced_us": (0.0, 5e6),
        "facts": {"ell_shapes": [[600, 8], [400, 512]],
                  "ell_hub_rows": 24},
        "peaks": {"hbm_bytes_per_s": 819e9}}
    record.update(over)
    return record


def test_the_khop_readers_on_hand_made_records():
    record = _record()
    assert _layer("khop_count_kernel_ms")["reader"] == "trace_program"
    assert trace_program.read(_layer("khop_count_kernel_ms")["select"],
                              record) == pytest.approx(0.05)
    counted = _note("khop_counted_share")
    assert counted["reader"] == "flight_ratio"
    assert flight_ratio.read(counted["select"], record) == 1.0
    assert counter_delta.read(_layer("khop_hops_per_stmt")["select"],
                              record) == pytest.approx(22 / 6)
    assert counter_delta.read(_layer("khop_vertices_per_stmt")["select"],
                              record) == 100_000.0
    # 976 vertex rows x 16 B + 128 x 4 B a count, forty counts in 2 ms
    assert count_bytes.vertex_rows([[600, 8], [400, 512]], 24) == 976
    assert count_bytes.count_bytes([[600, 8], [400, 512]], 24, 128) \
        == 976 * 16 + 512
    assert _layer("khop_count_roofline")["reader"] == "count_roofline"
    assert count_roofline.read(
        _layer("khop_count_roofline")["select"], record) \
        == pytest.approx(100 * 40 * 16_128 / 819e9 / 0.002)
    # a CPU rehearsal has no peak to hold the program against
    assert count_roofline.read(_layer("khop_count_roofline")["select"],
                               _record(peaks=None)) is None


def test_the_cell_size_count_cannot_pass_its_roofline():
    """At the cell's table (646,081 vertex rows, 128 lanes) one count
    has to move 10.3 MB: 12.6 us at 819 GB/s."""
    shapes = [[385823, 8], [94937, 16], [30842, 32], [73934, 64],
              [9372, 128], [29473, 256], [45260, 512]]    # PERF.md section 5
    moved = count_bytes.count_bytes(shapes, 23_560, 128)
    assert moved == 646_081 * 16 + 512
    assert moved / 819e9 == pytest.approx(12.62e-6, rel=1e-3)


def test_the_khop_readers_read_nothing_on_a_program_without_them():
    """The parent: no jit_count in its trace, no ``counted`` on its
    tick records, no ``rt.count_distinct_*`` counter.  Each reader
    returns None (left out of the line, named on stderr) and does not
    raise."""
    record = _record(
        flight=[{"kind": "tick", "leaves": 3, "handed": 3}],
        counters={"before": {"rt.go_device": 1},
                  "after": {"rt.go_device": 5}},
        trace={"program_s": {"jit_hop": 1.5},
               "program_runs": {"jit_hop": 44}},
        trees=[])
    for name, reader in (("khop_count_kernel_ms", trace_program),
                         ("khop_count_roofline", count_roofline),
                         ("khop_hops_per_stmt", counter_delta),
                         ("khop_vertices_per_stmt", counter_delta)):
        assert reader.read(_layer(name)["select"], record) is None, name
    assert flight_ratio.read(_note("khop_counted_share")["select"],
                             record) is None
