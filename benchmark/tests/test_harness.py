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

from benchmark import (bytes_model, reference, run, selfcheck,  # noqa: E402
                       semantics, spans)
from benchmark.deploy import (Deployment, flags_set, label_data,  # noqa: E402
                              shipped_defaults)
from benchmark.readers import (flight_ratio, sides_roofline,  # noqa: E402
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
    assert out["compared"]["answered"] == {"value": 16, "at_least": 1}
    assert all(v["value"] == 0 for v in out["compared"].values()
               if "limit" in v)


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


def test_a_window_in_which_every_statement_failed_is_not_correct(
        monkeypatch, capsys):
    """Every statement refused (PR 28 saw windows of 17,031 and 22,198
    such): nothing was held to the reference, so nothing was found
    wrong, and the run must not say ``correct`` for that; the line
    names the number that fell short, and carries the run's wall and
    the reference's cost a statement like every line."""
    from benchmark import workload
    monkeypatch.setattr(workload, "response_problems",
                        lambda resp: ["error: refused (the test's)"])
    out = run.run_cell(run.resolve(SPEC, CELL), seed=4_200_000_017,
                       seconds=2.0, trace=False, device=CPU, tiny=True)
    assert out["attempted"] == out["failed"] > 0
    assert out["correct"] is False
    compared = out["compared"]
    assert compared["answered"] == {"value": 0, "at_least": 1}
    assert compared["responses"]["value"] == out["attempted"]
    assert all(v["value"] == 0 for v in compared.values() if "limit" in v)
    capsys.readouterr()
    assert run.finish(out, trace=False) == 0
    said = capsys.readouterr()
    line = json.loads(said.out.strip().splitlines()[-1])
    assert line["correct"] is False and list(line)[-1] == "compared"
    assert line["notes"]["not_correct"] == ["answered"]
    assert line["notes"]["wall_s"] > line["notes"]["reference_s"] >= 0
    assert line["notes"]["reference_ms_per_stmt"] == pytest.approx(
        1e3 * line["notes"]["reference_s"] / line["attempted"])
    assert 'compared answered: {"value": 0, "at_least": 1}' in said.err


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


@pytest.mark.parametrize("off,marked", [
    ({}, []),
    ({"khop_counted_share": 0.98}, ["khop_counted_share"]),
    ({"neigh_ridden_share": 0.0, "where_native_share": 0.5},
     ["neigh_ridden_share", "where_native_share"])])
def test_a_guard_that_reads_off_is_marked_on_stderr(capsys, off, marked):
    """What a retired per-layer guard watched is compared with nothing,
    and a run says so itself where it is not what every run read: one
    MARKED line a guard, as for a compile in the window."""
    out = {"correct": True, "attempted": 1, "failed": 0,
           "metrics": {"qps": {"value": 1.0, "unit": "stmt/s"}},
           "device": dict(CPU), "compared": {},
           "notes": {"compiles_in_window": 0, "guards_off": off}}
    assert run.finish(out, trace=False) == 0
    said = capsys.readouterr()
    lines = [ln for ln in said.err.splitlines() if ln.startswith("MARKED")]
    assert [ln.split()[1] for ln in lines] == marked
    assert json.loads(said.out.strip().splitlines()[-1])["correct"] is True


# ------------------------------------ (e) the hop roofline's reader
SHAPES = [[1000, 8], [10, 512]]         # 13,120 slots, 1,010 rows
SELECT = run.load_json(run.HERE, "layer_metrics", "hop_roofline.json")[
    "select"]
PULL = bytes_model.pull_bytes(SHAPES, 1, 4, 4, 16)
PULL2 = bytes_model.pull_bytes(SHAPES, 2, 4, 4, 16)


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
    # a stream whose OVER set has both signs (no hop one-sided): a
    # pull sweeps both tables and moves the carriers once
    ([{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 2 * 13_120,
       "hop_onesided": 0}], PULL2),
    # a tick that learned of no hop moves nothing, the next one does
    ([{"hop_reads": 0, "hop_sparse": 0, "hop_slots": 0},
      {"hop_reads": 1, "hop_sparse": 1, "hop_slots": 8}], 8 * 40),
])
def test_hop_roofline_counts_what_the_hops_visited(ticks, moved):
    assert PULL == 13_120 * 24 + 1_010 * 64
    assert PULL2 == 2 * 13_120 * 24 + 1_010 * 64
    assert SELECT["onesided"] == "hop_onesided"
    assert sides_roofline.read(SELECT, _record(ticks)) == \
        pytest.approx(_share(moved))
    # a record that says every hop was one-sided reads as one that does
    # not say: the five cells whose statements name one sign
    for t in ticks:
        t.setdefault("hop_onesided", t.get("hop_reads", 0))
    assert sides_roofline.read(SELECT, _record(ticks)) == \
        pytest.approx(_share(moved))


def test_hop_roofline_reads_nothing_where_there_is_nothing():
    tick = {"hop_reads": 1, "hop_sparse": 1, "hop_slots": 100}
    assert sides_roofline.read(SELECT, _record([tick])) is not None
    # a record from before the program reported its hops: the reader
    # cannot know what moved (until PR 45 it guessed one whole sweep)
    assert sides_roofline.read(SELECT, _record([tick, {"dur_us": 5}])) \
        is None
    # no tick inside the traced interval; no interval; no hop program in
    # the trace; hops that moved nothing: never 0 for a roofline share
    assert sides_roofline.read(SELECT, _record([tick], (5_000, 6_000))) \
        is None
    assert sides_roofline.read(SELECT, _record([tick], None)) is None
    assert sides_roofline.read(SELECT, _record([])) is None
    no_hop = _record([tick])
    no_hop["trace"] = {"program_s": {"jit_other": 1.0},
                       "program_runs": {"jit_other": 1}}
    assert sides_roofline.read(SELECT, no_hop) is None
    assert sides_roofline.read(SELECT, _record(
        [{"hop_reads": 0, "hop_sparse": 0, "hop_slots": 0}])) is None
    assert sides_roofline.read(SELECT, {**_record([tick]),
                                        "trace": None}) is None
    # a pull that reports fewer slots than the loaded table has counts
    # another table than the harness: no share of the wrong bytes
    short = {"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_119}
    assert sides_roofline.read(SELECT, _record([tick, short])) is None


def test_on_the_recorded_trace_one_sided_hops_read_what_the_parent_read():
    """``hop_roofline.*`` changed reader in PR 45 and must not have
    changed number where every hop reads one table (seven of the eight
    cells): the recorded v5e trace's ``jit_hop`` seconds, three
    one-sided tick records, and the parent's arithmetic
    (``bytes_model.visited_bytes`` as PR 44 had it, written out here)
    give the same float, to the digit."""
    from benchmark import reduce_trace
    reduced = reduce_trace.reduce(reduce_trace.read_planes(os.path.join(
        run.HERE, "recorded", "v5e_small.xplane.pb")))
    assert reduced["program_runs"]["jit_hop"] == 3.0
    ticks = [{"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_120},
             {"hop_reads": 1, "hop_sparse": 1, "hop_slots": 72},
             {"hop_reads": 1, "hop_sparse": 0, "hop_slots": 13_120}]
    for t in ticks:
        t["hop_onesided"] = t["hop_reads"]
    record = {**_record(ticks), "trace": reduced,
              "peaks": run.load_json(run.HERE, "peaks.json")["TPU v5 lite"]}
    table, rows = 13_120, 1_010
    pull = table * (4 + 4 + 16) + rows * 4 * 16
    parents = 0
    for t in ticks:
        pulls = t["hop_reads"] - t["hop_sparse"]
        parents += pulls * pull \
            + (t["hop_slots"] - pulls * table) * (4 + 4 + 2 * 16)
    want = 100.0 * parents / record["peaks"]["hbm_bytes_per_s"] \
        / reduced["program_s"]["jit_hop"]
    assert sides_roofline.read(SELECT, record) == want
    assert 0 < want < 105


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


# ------------------------------------------- (g) the phases of a tree
def test_the_phase_map_charges_a_span_as_the_program_does():
    """``tpu.where`` is assembly and ``tpu.count`` is fetch, as the
    program's own ``critical_path`` has them (until PR 45 both fell to
    ``other``); ``tpu.transfer`` went with the span (PR 44)."""
    from nebula_tpu.common import tracing
    assert spans.PHASE_OF == {
        name: "enqueue" if phase == tracing.PHASE_KERNEL else phase
        for name, phase in tracing._PHASE_OF.items()}
    assert "tpu.transfer" not in spans.PHASE_OF
    leaf = lambda name, start, dur: {  # noqa: E731
        "name": name, "start_us": start, "duration_us": dur, "tags": {},
        "children": []}
    tree = {"roots": [{"name": "graph.query", "start_us": 0,
                       "duration_us": 1_000, "tags": {}, "children": [
                           leaf("tpu.fetch", 100, 200),
                           leaf("tpu.count", 300, 50),
                           {"name": "tpu.assemble", "start_us": 400,
                            "duration_us": 500, "tags": {},
                            "children": [leaf("tpu.where", 450, 300)]}]}]}
    assert spans.phases(tree) == {
        "queue": 250, "mirror": 0, "enqueue": 0, "fetch": 250,
        "assemble": 500, "other": 0}


# ------------------------------------ (h) a configuration's requires
@pytest.mark.parametrize("requires,lacks", [
    ({"flags": ["go_dispatch_mode", "no_such_flag_of_a_later_pr"]},
     "flag 'no_such_flag_of_a_later_pr'"),
    ({"statements": ["GO FROM 1 OVER knows",
                     "GO FROM 1 OVER knows SIDEWAYS"]},
     "statement 'GO FROM 1 OVER knows SIDEWAYS'"),
    ({"counters": ["rt.go_device", "graph.continuous.seat_hops",
                   "rt.no_such_counter"]},
     "counter 'rt.no_such_counter'"),
], ids=["flag", "statement", "counter"])
def test_a_configuration_that_requires_what_the_program_lacks_ends_early(
        requires, lacks, monkeypatch, capsys):
    """Exit code 1 and no result line, before a vertex is generated or
    labelled; what the program HAS beside the missing name is not
    named."""
    from benchmark import deploy
    from benchmark.generators import kronecker

    def never(*a, **k):
        raise AssertionError("data was made before requires was asked")
    monkeypatch.setattr(deploy, "label_data", never)
    monkeypatch.setattr(kronecker, "generate", never)
    parts = run.resolve(SPEC, CELL)
    assert parts["config"]["generator"] == "kronecker"
    parts["config"] = {**parts["config"], "requires": requires}
    with pytest.raises(SystemExit) as ended:
        run.run_cell(parts, seed=4_500_000_011, seconds=2.0, trace=False,
                     device=CPU, tiny=True)
    assert ended.value.code == 1
    said = capsys.readouterr()
    missing = [line for line in said.err.splitlines()
               if "requires what this program lacks" in line]
    assert len(missing) == 1 and lacks in missing[0]
    for line in said.out.strip().splitlines():      # no result line
        assert "metrics" not in json.loads(line)


@pytest.mark.parametrize("entry", SPEC["configs"],
                         ids=[c["name"] for c in SPEC["configs"]])
def test_the_shipped_configurations_declare_and_this_program_has_it(entry):
    """Each configuration's ``requires`` is met on the empty space, and
    no set-up statement stands for a declaration any more: a schema is
    ``CREATE EDGE`` alone, but for the two pins that tests outside
    ``benchmark/`` still hold (PERF.md section 7, Left by PR 45)."""
    config = run.load_json(ROOT, entry["file"])
    requires = config.get("requires", {})
    assert set(requires) <= {"flags", "statements", "counters"}
    assert not [s for s in config["schema"] if s.startswith("EXPLAIN")]
    pins = [s for s in config["schema"] if not s.startswith("CREATE ")]
    held_outside = {
        "graph500-s20-path": ["find_path_max_paths"],
        "graph500-s20-neigh": ["go_dispatch_mode", "query_deadline_ms"]}
    assert [p.split(":")[1].split("=")[0] for p in pins] \
        == held_outside.get(entry["name"], [])
    assert set(held_outside.get(entry["name"], [])) \
        <= set(requires.get("flags", []))           # declared as well
    dep = Deployment(config, str(os.path.join(run.OUT_DIR, "requires")))
    try:
        with flags_set({**shipped_defaults(), **config["flags"]}):
            dep.start()
            assert dep.missing(requires) == []
            assert len(dep.missing({"flags": ["no_such_flag"],
                                    "counters": ["rt.no_such"]})) == 2
    finally:
        dep.stop()


# ----------------------- (i) the rehearsal compares rows before forms
def test_an_answer_is_its_rows_whatever_form_it_came_in():
    cols = (np.array([5, 3, 3, 9]), np.array([1, 2, 2, 4]))
    rows = [(3, 2), (9, 4), (5, 1), (3, 2)]
    assert selfcheck.row_multiset(cols) == selfcheck.row_multiset(rows)
    assert not reference.same_rows(cols, rows)      # the form differs
    assert selfcheck.row_multiset(cols) != selfcheck.row_multiset(rows[:-1])
    assert selfcheck.row_multiset((np.array([], np.int64),)) \
        == selfcheck.row_multiset([]) == []
    assert selfcheck.check_comparator() == []


@pytest.mark.parametrize("cell", ["graph500-s20-where.filtered16",
                                  "graph500-s20-neigh.rows16"])
def test_the_rehearsal_holds_the_cpu_executor_to_rows_not_forms(cell):
    """The CPU executor hands a filtered GO's rows, and a DISTINCT's,
    as tuples; until PR 45 ``python3 -m benchmark.selfcheck`` exited 1
    on these two cells with every row equal."""
    only = {**SPEC, "workloads": [w for w in SPEC["workloads"]
                                  if w["name"] == cell]}
    assert selfcheck.check_reference_against_cpu_executor(only) == []


def test_no_kind_is_named_in_the_harness():
    kinds = [f[:-3] for f in os.listdir(os.path.join(run.HERE, "semantics"))
             if f.endswith(".py") and not f.startswith("_")]
    assert "go" in kinds and "go_count" in kinds
    for name in ("run.py", "workload.py", "reference.py"):
        text = open(os.path.join(run.HERE, name)).read()
        for kind in kinds:
            assert f'"{kind}"' not in text and f"'{kind}'" not in text \
                and f"def {kind}(" not in text, (name, kind)
