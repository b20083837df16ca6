"""The harness takes a deployment's statement shapes and a PR's
per-layer metrics as files (run: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``).  CPU only: no number here is a device number.
"""
from __future__ import annotations

import copy
import hashlib
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_model, reference, run, semantics  # noqa: E402
from benchmark.deploy import label_data  # noqa: E402
from benchmark.readers import (flight_ratio, slots_roofline,  # noqa: E402
                               span_tag)
from benchmark.workload import Mix  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SPEC = run.load_json(ROOT, "BENCHMARK.json")
CELL = SPEC["workloads"][0]["name"]

# a statement shape that exists only here: GO from a PAIR of vertices
PAIR_GO = '''
import numpy as np
ARITY = 2


def answer(graph, semantics, key):
    assert isinstance(key, tuple) and len(key) == ARITY
    frontier = np.unique(np.asarray(key, np.int64))
    for _ in range(int(semantics["steps"]) - 1):
        frontier = np.unique(graph.dst[graph.edge_positions(frontier)])
    return (graph.dst[graph.edge_positions(frontier)],)
'''
PAIR_CLASS = {
    "template": "GO 2 STEPS FROM {v0}, {v1} OVER knows YIELD knows._dst",
    "semantics": {"kind": "pair_go", "steps": 2},
    "traversal": True, "served_counter": "rt.go_device"}


@pytest.fixture
def pair_kind(tmp_path, monkeypatch):
    """``semantics/pair_go.py`` as one more file of the package, in a
    directory of the test's own."""
    (tmp_path / "pair_go.py").write_text(PAIR_GO)
    monkeypatch.setattr(semantics, "__path__",
                        list(semantics.__path__) + [str(tmp_path)])
    yield
    sys.modules.pop("benchmark.semantics.pair_go", None)


def _tiny(cell: str, seed: int):
    parts = run.resolve(SPEC, cell)
    config, traffic = parts["config"], dict(parts["traffic"])
    traffic.update(traffic.get("selfcheck", {}))
    gen = importlib.import_module(
        f"benchmark.generators.{config['generator']}").generate(
            {**config["generator_params"],
             **config["selfcheck"]["generator_params"]},
            int(config["structure_seed"]))
    return parts, traffic, label_data(gen, seed)


# ------------------------------------------------ (a) a kind of its own
def test_a_kind_added_as_a_file_is_offered_with_its_places(pair_kind):
    parts, traffic, data = _tiny(CELL, 77)
    traffic["classes"] = {**traffic["classes"], "pair": PAIR_CLASS}
    traffic["groups"] = [{"loop": "open", "rate_per_s": 40, "workers": 4,
                          "shares": {"go2": 0.5, "pair": 0.5}}]
    mix = Mix(traffic, data, int(parts["config"]["structure_seed"]), 77, 2.0)
    seq, pair = mix.groups[0]["measured"], mix.class_names.index("pair")
    assert mix.arity == [1, 1, 2] and seq["key"].shape == (80, 2)
    seen = set()
    for i in range(80):
        ci, key = mix.at(seq, i)
        stmt = mix.statement(ci, key)
        seen.add(ci)
        if ci == pair:
            assert isinstance(key, tuple) and len(key) == 2
            assert stmt == (f"GO 2 STEPS FROM {key[0]}, {key[1]} OVER knows "
                            f"YIELD knows._dst")
        else:
            assert isinstance(key, int) and f"FROM {key} OVER" in stmt
    assert seen == {mix.class_names.index("go2"), pair}
    # a warm-up statement of such a class carries one key, whatever the
    # number of start vertices the step asks for
    for ci, key in mix.warm(4, 9, 0) + mix.warm(4, 1, 1):
        assert len(key) == (2 if ci == pair else 9 if len(key) > 2 else 1)
        mix.statement(ci, key)


def _pair_parts() -> dict:
    """The first cell with its traffic's classes replaced by the pair
    class alone, 8 statements a second."""
    parts = run.resolve(SPEC, CELL)
    parts["traffic"] = {
        **parts["traffic"], "classes": {"pair": PAIR_CLASS},
        "groups": [{"loop": "open", "rate_per_s": 8, "workers": 8,
                    "shares": {"pair": 1.0}}]}
    return parts


def test_a_kind_added_as_a_file_is_answered_and_compared(pair_kind):
    parts = _pair_parts()
    out = run.run_cell(parts, seed=2_900_000_011, seconds=2.0, trace=False,
                       device=CPU, tiny=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["compared"]["responses"]["value"] == 16
    assert list(out)[-1] == "compared"
    assert all(v["value"] == 0 for k, v in out["compared"].items()
               if k != "responses")


def test_a_wrong_answer_of_such_a_kind_is_not_correct(pair_kind,
                                                      monkeypatch):
    parts = _pair_parts()
    module = reference.semantics_module("pair_go")
    real = module.answer
    monkeypatch.setattr(module, "answer", lambda g, s, k: tuple(
        c[:-1] for c in real(g, s, k)))
    out = run.run_cell(parts, seed=2_900_000_011, seconds=2.0, trace=False,
                       device=CPU, tiny=True)
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"]["digest_mismatches"]["value"] > 0


# ------------------------------------------------- (b) an unknown kind
def test_an_unknown_kind_fails_in_resolve(monkeypatch):
    real = run.load_json

    def load(*path):
        out = real(*path)
        if path[-2:] == ("traffic", SPEC["workloads"][0]["traffic"]
                         + ".json"):
            out = copy.deepcopy(out)
            next(iter(out["classes"].values()))["semantics"]["kind"] = \
                "no_such_kind"
        return out
    monkeypatch.setattr(run, "load_json", load)
    with pytest.raises(SystemExit, match="no_such_kind"):
        run.resolve(SPEC, CELL)
    with pytest.raises(ValueError, match="semantics/no_such_kind.py"):
        reference.semantics_module("no_such_kind")


# --------------------------- (c) arity 1 is offered as the parent did
# sha256 over every statement of both phases, the due times and the
# warm-up steps, computed on the parent tree (7ff087f) at the selfcheck
# size with seconds = 3.0
PARENT_SEQUENCES = {
    ("lone8", 11):
        "8653b8d83081592d7689efafd0c0c4bd50b65f279372ed6afdcda78884067024",
    ("lone8", 2_345_678_901):
        "abba117d869de3a1ee19005bd802fd808ec8d00440fadd1bb8787bd6739473ac",
    ("lone8", 3_999_999_999):
        "cdd51cfa9229c1420845390c4981c7b3e9c9d1c8f96708bc241034e1cd954d3b",
    ("closed64", 11):
        "1805408fd7f35b3a99cb634153e6a0f85fca679fd694cd37812bd8c628155445",
    ("closed64", 2_345_678_901):
        "835701659e6bcf828fed17bd0c8262b6a94fa848c3d5a65b2d96e5b070ad452c",
    ("closed64", 3_999_999_999):
        "72f4fe8ba390867a7a314cbb8399ee4fa63c07ffd6061ebaad136dcb3f2e0e92",
}


@pytest.mark.parametrize("traffic_name,seed", sorted(PARENT_SEQUENCES))
def test_the_same_seed_offers_what_the_parent_offered(traffic_name, seed):
    cell = next(w["name"] for w in SPEC["workloads"]
                if w["traffic"] == traffic_name)
    parts, traffic, data = _tiny(cell, seed)
    mix = Mix(traffic, data, int(parts["config"]["structure_seed"]), seed,
              3.0)
    h = hashlib.sha256()
    for g in mix.groups:
        for phase in ("measured", "warmup"):
            seq = g[phase]
            for i in range(len(seq["cls"])):
                h.update(mix.statement(*mix.at(seq, i)).encode() + b"\n")
            if "due" in seq:
                h.update(seq["due"].tobytes())
    warm = traffic["warmup"]
    steps = [(1, int(k)) for k in warm["starts"]] \
        + [(int(k), 1) for k in warm["bursts"]]
    for i, (statements, starts) in enumerate(steps):
        for ci, key in mix.warm(statements, starts, i):
            h.update(mix.statement(ci, key).encode() + b"\n")
    assert h.hexdigest() == PARENT_SEQUENCES[traffic_name, seed]


# -------------- (d) a reader with nothing to read; a trace that is absent
NOTHING = {"name": "nothing_there.lat", "unit": "ms", "better": "lower",
           "source": "program_counter", "layer": "a later PR's",
           "moves": "trav_p50_ms", "reader": "flight_field",
           "select": {"kind": "tick", "field": "a_field_of_a_later_pr",
                      "reduce": "mean", "scale": 1}}


def _traced(monkeypatch, with_trace: bool) -> dict:
    from benchmark import reduce_trace
    parts = run.resolve(SPEC, CELL)
    parts["per_layer"] = parts["per_layer"] + [NOTHING]
    if with_trace:
        # a CPU profile has no device plane: stand one in
        monkeypatch.setattr(reduce_trace, "read_planes", lambda path: {
            "devices": {}, "sync_ns": 0.0})
        monkeypatch.setattr(reduce_trace, "reduce", lambda *a, **k: {
            "window_s": 1.0, "busy_s": 0.25,
            "program_s": {"jit_hop": 0.2}, "program_runs": {"jit_hop": 8.0},
            "device_ops": [["%fusion.1 fusion", 0.2]], "idle_gaps_ns": []})
    return run.run_cell(parts, seed=2_900_000_023, seconds=2.0, trace=True,
                        device=dict(CPU), tiny=True)


def test_a_listed_metric_with_nothing_to_read_is_left_out(monkeypatch,
                                                          capsys):
    out = _traced(monkeypatch, with_trace=True)
    assert "nothing_there.lat" in out["notes"]["missing_per_layer"]
    capsys.readouterr()
    assert run.finish(out, trace=True) == 0
    said = capsys.readouterr()
    line = json.loads(said.out.strip().splitlines()[-1])
    assert "nothing_there.lat" not in line["metrics"]
    assert {"tick_ms.lat", "pump_unpack_ms.lat", "seat_wait_ms.lat",
            "hop_sparse_share.lat"} <= set(line["metrics"])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(line) and list(line)[-1] == "compared"
    assert line["device"]["busy_s"] == 0.25
    assert "nothing_there.lat" in said.err
    assert said.err.strip().splitlines()[-1].startswith("compared ")


def test_a_traced_run_whose_trace_is_absent_prints_no_result(monkeypatch,
                                                             capsys):
    out = _traced(monkeypatch, with_trace=False)
    assert out["notes"]["trace_missing"] is True
    capsys.readouterr()
    assert run.finish(out, trace=True) == 1
    said = capsys.readouterr()
    assert "no result" in said.err
    for line in said.out.strip().splitlines():
        assert "metrics" not in json.loads(line)


def test_a_traced_run_in_which_no_reader_read_prints_no_result(capsys):
    out = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
           "device": dict(CPU), "compared": {},
           "notes": {"compiles_in_window": 0,
                     "missing_per_layer": ["x.lat"]}}
    assert run.finish(out, trace=True) == 1
    assert "no per-layer metric" in capsys.readouterr().err


# ------------------------------------------------- (e) the slots reader
SHAPES = [[1000, 8], [10, 512]]         # 13,120 slots, 1,010 rows
SELECT = run.load_json(run.HERE, "layer_metrics", "hop_roofline.json")[
    "select"]
PULL = bytes_model.hop_bytes(SHAPES, 4, 4, 16)


def _record(ticks, interval=(1_000, 2_000), seconds=0.5):
    kernel = {"name": "tpu.kernel", "start_us": 0, "duration_us": 1,
              "tags": {"kind": "ell_go_hop", "width": 128}, "children": []}
    return {"trace": {"program_s": {"jit_hop": seconds, "jit_other": 9.0},
                      "program_runs": {"jit_hop": 4, "jit_other": 1}},
            "traced_us": interval, "trees": [{"roots": [kernel]}],
            "flight": [{"kind": "tick", "time_us": 1_500, **t}
                       for t in ticks] + [{"kind": "dispatch",
                                           "time_us": 1_500}],
            "facts": {"ell_shapes": SHAPES, "ell_index_itemsize": 4,
                      "ell_etype_itemsize": 4},
            "peaks": {"hbm_bytes_per_s": 1e9}}


def _share(moved_bytes, seconds=0.5):
    return 100.0 * moved_bytes / 1e9 / seconds


@pytest.mark.parametrize("ticks,moved", [
    # all pushes: the slots the hops visited, 4 + 4 + 16 + 16 B each
    ([{"hop_reads": 1, "hop_sparse": 1, "hop_slots": 100},
      {"hop_reads": 2, "hop_sparse": 2, "hop_slots": 400}], 500 * 40),
    # all pulls: the whole table each, as before the re-basing
    ([{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_120},
      {"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_120}], 2 * PULL),
    # one of each in one tick
    ([{"hop_reads": 2, "hop_sparse": 1, "hop_slots": 13_120 + 64}],
     PULL + 64 * 40),
    # a record from before the program reported its hops: one sweep
    ([{"dur_us": 5}], PULL),
    # a tick that learned of no hop moves nothing, the next one does
    ([{"hop_reads": 0, "hop_sparse": 0, "hop_slots": 0},
      {"hop_reads": 1, "hop_sparse": 1, "hop_slots": 8}], 8 * 40),
])
def test_slots_roofline_counts_what_the_hops_visited(ticks, moved):
    assert PULL == 13_120 * 24 + 1_010 * 64
    assert slots_roofline.read(SELECT, _record(ticks)) == \
        pytest.approx(_share(moved))


def test_slots_roofline_reads_nothing_where_there_is_nothing():
    tick = {"hop_reads": 1, "hop_sparse": 1, "hop_slots": 100}
    assert slots_roofline.read(SELECT, _record([tick])) is not None
    # no tick inside the traced interval; no interval; no hop program in
    # the trace; hops that moved nothing: never 0 for a roofline share
    assert slots_roofline.read(SELECT, _record([tick], (5_000, 6_000))) \
        is None
    assert slots_roofline.read(SELECT, _record([tick], None)) is None
    assert slots_roofline.read(SELECT, _record([])) is None
    no_hop = _record([tick])
    no_hop["trace"] = {"program_s": {"jit_other": 1.0},
                       "program_runs": {"jit_other": 1}}
    assert slots_roofline.read(SELECT, no_hop) is None
    assert slots_roofline.read(SELECT, _record(
        [{"hop_reads": 0, "hop_sparse": 0, "hop_slots": 0}])) is None
    assert slots_roofline.read(SELECT, {**_record([tick]),
                                        "trace": None}) is None
    # a pull that reports fewer slots than the loaded table has counts
    # another table than the harness: no share of the wrong bytes
    short = {"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_119}
    assert slots_roofline.read(SELECT, _record([tick, short])) is None


def test_flight_ratio_divides_two_summed_fields():
    select = run.load_json(run.HERE, "layer_metrics",
                           "hop_sparse_share.json")["select"]
    rec = _record([{"hop_reads": 2, "hop_sparse": 2},
                   {"hop_reads": 2, "hop_sparse": 1}, {"dur_us": 3}])
    assert flight_ratio.read(select, rec) == pytest.approx(0.75)
    assert flight_ratio.read(select, _record([{"dur_us": 3}])) is None
    assert flight_ratio.read(select, _record(
        [{"hop_reads": 0, "hop_sparse": 0}])) is None


# --------------------------------------------------------- (f) span_tag
def test_span_tag_reads_one_tag_of_one_named_span():
    def tree(*markers):
        return {"roots": [{"name": "graph.execute", "tags": {},
                           "children": [
                               {"name": "graph.continuous", "tags": m,
                                "children": []} for m in markers]}]}
    record = {"trees": [tree({"seat_wait_us": 100, "ride_us": 7}),
                        tree({"seat_wait_us": 300}, {"lane": 3}),
                        tree({"seat_wait_us": 800}),
                        {"roots": [{"name": "graph.parse", "tags": {
                            "seat_wait_us": 10 ** 9}, "children": []}]}]}
    select = {"span": "graph.continuous", "tag": "seat_wait_us",
              "reduce": "median", "scale": 0.001}
    assert span_tag.read(select, record) == pytest.approx(0.3)
    assert span_tag.read({**select, "tag": "ride_us"}, record) == \
        pytest.approx(0.007)
    assert span_tag.read({**select, "tag": "wake_us"}, record) is None
    assert span_tag.read({**select, "span": "pump.tick"}, record) is None
    assert span_tag.read(select, {"trees": []}) is None


def test_latency_is_read_end_to_end_and_as_a_traced_runs_own():
    from benchmark.quantities import QUANTITIES
    from benchmark.readers import window_latency
    window = {"deadline_s": 30.0, "records": [
        {"traversal": name != "point", "due": 1.0, "done": 1.0 + ms / 1e3,
         "failed": name == "late"}
        for name, sample in (("a", range(1, 101)), ("late", [7]),
                             ("point", [9e3] * 50)) for ms in sample]}
    median = run.load_json(run.HERE, "end_metrics", "trav_p50_ms.json")
    # a failed statement enters the sample at the deadline
    assert QUANTITIES[median["quantity"]](median, window) == \
        pytest.approx(51)
    mean = run.load_json(run.HERE, "layer_metrics",
                         "trav_mean_ms.json")["select"]
    assert window_latency.read(mean, {"window": window}) == \
        pytest.approx((5050 + 30_000) / 101)
    select = run.load_json(run.HERE, "layer_metrics",
                           "trav_p95_ms.json")["select"]
    assert window_latency.read(select, {"window": window}) == \
        pytest.approx(96)
    assert window_latency.read(select, {"window": {
        "deadline_s": 30.0, "records": []}}) is None


def test_no_kind_is_named_in_the_harness():
    kinds = [f[:-3] for f in os.listdir(os.path.join(run.HERE, "semantics"))
             if f.endswith(".py") and not f.startswith("_")]
    assert "go" in kinds and "go_count" in kinds
    for name in ("run.py", "workload.py", "reference.py"):
        text = open(os.path.join(run.HERE, name)).read()
        for kind in kinds:
            assert f'"{kind}"' not in text and f"'{kind}'" not in text \
                and f"def {kind}(" not in text, (name, kind)
