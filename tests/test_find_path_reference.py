"""FIND SHORTEST PATH three ways on one loaded graph: the device path
(``tpu_backend=True`` on CPU jax), graphd's CPU executor
(``storage_backend=cpu``) and the benchmark's plain reference
(``benchmark/semantics/shortest_path.py``) must answer the same rows,
cut at ``find_path_max_paths`` by the same order over vertex ids; and the
in-edge order the device path's host half walks is built once a mirror
generation, at its first path statement.  Since PR 46 the OVER set's
signs are honoured: ``OVER knows BIDIRECT`` is held the same three ways
to ``benchmark/semantics/shortest_path_bidirect.py`` (and that to a
brute-force enumeration), ``REVERSELY`` to the forward answer on the
transposed graph, and a forward statement's rows to what PR 46's parent
gave, byte for byte.
"""
from __future__ import annotations

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, run  # noqa: E402
from benchmark.deploy import (Deployment, flags_set, label_data,  # noqa: E402
                              shipped_defaults)
from benchmark.tests.test_bipath import _bipath_brute  # noqa: E402

CAP = 7             # find_path_max_paths for these tests: many pairs pass it
SEM = {"kind": "shortest_path", "max_steps": 5, "edge": "knows"}
SCALE = 10          # 1,024 labels; the built cases take ids above them
TOP = 1 << SCALE
# built onto the generated graph: a ladder of 10 x 10 least paths from
# A to B, a chain of six steps, and a vertex nothing points at
A, B, CHAIN, LONELY = TOP + 1, TOP + 2, TOP + 100, TOP + 200
BUILT = [(A, TOP + 10 + i) for i in range(10)] \
    + [(TOP + 10 + i, TOP + 30 + j) for i in range(10) for j in range(10)] \
    + [(TOP + 30 + j, B) for j in range(10)] \
    + [(CHAIN + i, CHAIN + i + 1) for i in range(6)] \
    + [(LONELY, A)]


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    spec = run.load_json(ROOT, "BENCHMARK.json")
    config = run.load_json(ROOT, next(
        c["file"] for c in spec["configs"]
        if c["name"] == "graph500-s20-path"))
    gen = importlib.import_module(
        f"benchmark.generators.{config['generator']}").generate(
            {**config["generator_params"], "scale": SCALE, "edgefactor": 8},
            int(config["structure_seed"]))
    data = label_data(gen, seed=2_700_000_027)
    src, dst = (np.asarray(c, np.int64) for c in zip(*BUILT))
    data["src"] = np.concatenate([data["src"], src])
    data["dst"] = np.concatenate([data["dst"], dst])
    data["edge_prop_idx"] = np.concatenate(
        [data["edge_prop_idx"], np.zeros(len(src), np.int64)])
    dep = Deployment(config, str(tmp_path_factory.mktemp("path")))
    with flags_set({**shipped_defaults(), **config["flags"]}):
        dep.load(data)      # its set-up statements pin the cap at 1,000
        graph = reference.Graph(data["src"], data["dst"],
                                data["edge_prop_table"],
                                data["edge_prop_idx"])
        with flags_set({"find_path_max_paths": CAP}):
            yield dep, dep.client(), graph, data
        dep.stop()


def _rows(client, stmt: str, backend: str):
    with flags_set({"storage_backend": backend}):
        resp = client.execute(stmt)
    assert resp.ok() and not resp.warnings and resp.completeness == 100, \
        (stmt, resp.error_msg, resp.warnings)
    return sorted(tuple(r) for r in resp.rows)


def _want(graph, a: int, targets, sem=SEM) -> list:
    """The reference's answer to one start and several targets: the
    targets by ascending id, each given what the cap has left."""
    rows = []
    for b in sorted(set(targets)):
        rows += graph.answer({**sem, "max_paths": CAP - len(rows)}, (a, b)) \
            if len(rows) < CAP else []
    return sorted(rows)


def _pairs(data, n: int):
    rng = np.random.default_rng(27)
    cand = data["perm"][data["structural_with_out_edge"]]
    return [(int(a), int(b)) for a, b in
            zip(rng.choice(cand, n), rng.choice(cand, n))]


PAIRS = [("built: more paths than the cap", A, [B]),
         ("built: a = b", A, [A]),
         ("built: the target has no in-edge", A, [LONELY]),
         ("built: unreachable", CHAIN, [B]),
         ("built: exactly five steps", CHAIN, [CHAIN + 5]),
         ("built: six steps", CHAIN, [CHAIN + 6]),
         ("built: one step", LONELY, [A]),
         ("built: several targets over the cap", A,
          [TOP + 32, B, TOP + 30, TOP + 31])]


@pytest.mark.parametrize("what, a, targets", PAIRS, ids=[p[0] for p in PAIRS])
def test_built_cases_agree(loaded, what, a, targets):
    _dep, client, graph, _data = loaded
    stmt = (f"FIND SHORTEST PATH FROM {a} TO "
            f"{', '.join(map(str, targets))} OVER knows UPTO 5 STEPS")
    want = _want(graph, a, targets)
    assert _rows(client, stmt, "tpu") == want
    assert _rows(client, stmt, "cpu") == want
    if what.endswith("than the cap"):
        assert len(want) == CAP
        # read from B backwards: the smallest vertex before B, then the
        # vertices before that one by ascending id
        assert want == sorted(
            (f"{A} <knows,0> {TOP + 10 + i} <knows,0> {TOP + 30} "
             f"<knows,0> {B}",) for i in range(CAP))


@pytest.mark.parametrize("block", range(4))
def test_generated_pairs_agree(loaded, block):
    dep, client, graph, data = loaded
    before = dep.rt.stats["path_device"]
    capped = reached = 0
    for a, b in _pairs(data, 80)[block * 20:(block + 1) * 20]:
        stmt = f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows UPTO 5 STEPS"
        want = _want(graph, a, [b])
        assert _rows(client, stmt, "tpu") == want, stmt
        assert _rows(client, stmt, "cpu") == want, stmt
        reached += bool(want)
        capped += len(want) == CAP
    assert dep.rt.stats["path_device"] == before + 20
    assert reached >= 10 and capped >= 3    # the cap's order is exercised


def test_several_starts_and_targets_agree_between_backends(loaded):
    _dep, client, _graph, data = loaded
    pairs = _pairs(data, 12)
    stmt = (f"FIND SHORTEST PATH FROM {pairs[0][0]}, {pairs[1][0]} TO "
            f"{', '.join(str(b) for _, b in pairs)} OVER knows UPTO 4 STEPS")
    got = _rows(client, stmt, "tpu")
    assert got == _rows(client, stmt, "cpu") and len(got) == CAP


def test_find_all_path_agrees_between_backends(loaded):
    _dep, client, _graph, _data = loaded
    stmt = f"FIND ALL PATH FROM {A} TO {B}, {TOP + 30} OVER knows UPTO 3 STEPS"
    got = _rows(client, stmt, "tpu")
    assert got == _rows(client, stmt, "cpu") and len(got) == CAP


def test_the_in_edge_order_is_built_once_a_mirror_generation(loaded):
    dep, client, graph, data = loaded
    rt = dep.rt
    for a, b in _pairs(data, 6):
        client.execute(f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows")
    assert rt.stats["path_index_builds"] == 1
    mirror = rt.mirror(dep.cluster.graph_meta_client.get_space_id_by_name(
        dep.config["space"]).value())
    assert len(mirror._path_index) == 1
    # a write moves the generation: the next path statement builds the
    # order of the new one, and reads the new edge
    far = CHAIN + 6
    assert _rows(client, f"FIND SHORTEST PATH FROM {B} TO {far} OVER knows",
                 "tpu") == []
    resp = client.execute(f"INSERT EDGE knows(w) VALUES {B} -> {far}:(0.5)")
    assert resp.ok(), resp.error_msg
    assert _rows(client, f"FIND SHORTEST PATH FROM {B} TO {far} OVER knows",
                 "tpu") == [(f"{B} <knows,0> {far}",)]
    assert rt.stats["path_index_builds"] == 2
    client.execute(f"FIND SHORTEST PATH FROM {A} TO {far} OVER knows")
    assert rt.stats["path_index_builds"] == 2


def test_callers_at_once_build_the_in_edge_order_once(loaded):
    """More callers than cores meet a generation that has no in-edge
    order yet: one of them builds it, all of them answer the same."""
    import sys
    import threading
    dep, client, graph, _data = loaded
    far = CHAIN + 3
    resp = client.execute(f"INSERT EDGE knows(w) VALUES {B} -> {far}:(0.5)")
    assert resp.ok(), resp.error_msg            # the generation moves
    builds = dep.rt.stats["path_index_builds"]
    stmt = f"FIND SHORTEST PATH FROM {A} TO {B} OVER knows UPTO 5 STEPS"
    want, got, clients = _want(graph, A, [B]), [], \
        [dep.client() for _ in range(24)]

    def call(c):
        got.append(sorted(tuple(r) for r in c.execute(stmt).rows))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=call, args=(c,))
                   for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * 24
    assert dep.rt.stats["path_index_builds"] == builds + 1


def test_a_traced_statement_says_where_its_time_went(loaded):
    """The rider's marker of the windowed tier, the walk's own thread
    time, and the dispatch record's stages (written traced or not)."""
    from benchmark.spans import walk
    from nebula_tpu.common import tracing
    from nebula_tpu.common.flight import recorder
    dep, client, _graph, _data = loaded
    tracing.trace_store.clear_for_tests()
    with flags_set({"trace_sample_rate": 1.0}):
        resp = client.execute(
            f"FIND SHORTEST PATH FROM {A} TO {B} OVER knows UPTO 5 STEPS")
    assert resp.ok() and len(resp.rows) == CAP
    nodes = [n for s in tracing.trace_store.summaries()
             for n in walk(tracing.trace_store.tree(int(s["id"], 16)))]
    rode = [n["tags"] for n in nodes if n["name"] == "graph.batched"]
    assert len(rode) == 1 and rode[0]["method"] == "bfs_batch_dispatch"
    assert rode[0]["riders"] == 1
    assert all(rode[0][k] >= 0
               for k in ("pool_wait_us", "run_us", "wake_us"))
    walked = [n for n in nodes if n["name"] == "tpu.path_reconstruct"]
    assert len(walked) == 1 and walked[0]["tags"]["capped"]
    assert 0 <= walked[0]["tags"]["cpu_us"] <= walked[0]["duration_us"] + 1000
    # the in-edge order is there before the walk's span opens
    assert not [n for n in walk(walked[0]) if n["name"] == "tpu.path_index"]
    record = max((r for r in recorder.dump(limit=1 << 16)
                  if r.get("kernel") == "ell_bfs"),
                 key=lambda r: r["time_us"])
    assert record["queries"] == 1 and 1 <= record["levels"] <= 5
    stages = [record[k] for k in ("upload_us", "enqueue_us", "fetch_us")]
    assert all(us >= 0 for us in stages)
    assert sum(stages) <= rode[0]["run_us"] + 1000


def test_a_space_that_runs_no_path_statement_builds_none():
    from nebula_tpu.cluster import LocalCluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        g = c.client()
        for stmt in ("CREATE SPACE quiet(partition_num=2, replica_factor=1)",
                     "USE quiet", "CREATE EDGE knows(w double)"):
            assert g.execute(stmt).ok(), stmt
            c.refresh_all()
        assert g.execute("INSERT EDGE knows(w) VALUES 1 -> 2:(0.5), "
                         "2 -> 3:(0.5)").ok()
        resp = g.execute("GO 2 STEPS FROM 1 OVER knows YIELD knows._dst")
        assert resp.ok() and [tuple(r) for r in resp.rows] == [(3,)]
        rt = c.tpu_runtime
        assert rt.stats["go_device"] >= 1
        assert rt.stats["path_index_builds"] == 0
        assert all(not m._path_index for m in rt.mirrors.values())
    finally:
        c.stop()


def test_the_cap_is_a_managed_flag_the_configuration_pins(loaded):
    """The path deployment's set-up states its cap through the
    program's own front door; a graphd that manages no such flag
    refuses the statement (so the deployment ends in set-up there),
    and one that does cuts every later answer by it."""
    _dep, client, graph, _data = loaded
    config = run.load_json(ROOT, "benchmark", "configs",
                           "graph500-s20-path.json")
    pin = "UPDATE CONFIGS graph:find_path_max_paths=1000"
    assert pin in config["schema"]
    refused = client.execute("UPDATE CONFIGS graph:find_path_no_such_cap=3")
    assert not refused.ok()
    stmt = f"FIND SHORTEST PATH FROM {A} TO {B} OVER knows UPTO 5 STEPS"
    try:
        assert client.execute(pin).ok()
        assert len(_rows(client, stmt, "tpu")) == 100
        assert len(_rows(client, stmt, "cpu")) == 100
        assert client.execute(pin.replace("1000", "3")).ok()
        want = sorted(graph.answer({**SEM, "max_paths": 3}, (A, B)))
        assert _rows(client, stmt, "tpu") == want and len(want) == 3
        assert _rows(client, stmt, "cpu") == want
    finally:
        assert client.execute(pin.replace("1000", str(CAP))).ok()


# ====================================================================
# the OVER set's signs (PR 46): BIDIRECT, REVERSELY, and forwards as it
# was
# ====================================================================
BI_SEM = {"kind": "shortest_path_bidirect", "max_steps": 5, "edge": "knows"}
BI_TOP = 1 << 11    # the selfcheck size: 2,048 labels, built ids above
# built onto the generated graph, a component of its own: P and Q stored
# in both orders, R that only points (at Q), a diamond from S to T whose
# nine least paths cross their edges both ways, and CHAIN_BI, six steps
# long
P, Q, R, S, T, CHAIN_BI = (BI_TOP + k for k in (1, 2, 3, 10, 20, 100))
M1, M2, M3, M4 = (BI_TOP + 11 + i for i in range(4))
BI_BUILT = [(P, Q), (Q, P), (R, Q)] \
    + [(S, m) for m in (M1, M2, M3, M4)] + [(M1, S), (M3, S)] \
    + [(m, T) for m in (M1, M2, M3, M4)] + [(T, M1), (T, M2)] \
    + [(CHAIN_BI + i, CHAIN_BI + i + 1) for i in range(6)]


@pytest.fixture(scope="module")
def loaded_bi(tmp_path_factory):
    """The undirected path deployment at its selfcheck size with the
    built component beside it: (deployment, client, the reference's
    graph, its transpose, the labelled data)."""
    spec = run.load_json(ROOT, "BENCHMARK.json")
    config = run.load_json(ROOT, next(
        c["file"] for c in spec["configs"]
        if c["name"] == "graph500-s20-bipath"))
    gen = importlib.import_module(
        f"benchmark.generators.{config['generator']}").generate(
            {**config["generator_params"],
             **config["selfcheck"]["generator_params"]},
            int(config["structure_seed"]))
    data = label_data(gen, seed=4_600_000_046)
    src, dst = (np.asarray(c, np.int64) for c in zip(*BI_BUILT))
    data["src"] = np.concatenate([data["src"], src])
    data["dst"] = np.concatenate([data["dst"], dst])
    data["edge_prop_idx"] = np.concatenate(
        [data["edge_prop_idx"], np.zeros(len(src), np.int64)])
    dep = Deployment(config, str(tmp_path_factory.mktemp("bipath")))
    with flags_set({**shipped_defaults(), **config["flags"]}):
        dep.start()
        assert dep.missing(config["requires"]) == []
        dep.load(data)
        graph, transposed = (
            reference.Graph(data[s], data[d], data["edge_prop_table"],
                            data["edge_prop_idx"])
            for s, d in (("src", "dst"), ("dst", "src")))
        with flags_set({"find_path_max_paths": CAP}):
            yield dep, dep.client(), graph, transposed, data
        dep.stop()


BI_PAIRS = [
    ("a pair stored in both orders is two paths", P, [Q],
     [f"{P} <-knows,0> {Q}", f"{P} <knows,0> {Q}"]),
    ("the target has an out-edge and no in-edge", P, [R],
     [f"{P} <-knows,0> {Q} <-knows,0> {R}",
      f"{P} <knows,0> {Q} <-knows,0> {R}"]),
    ("a = b", P, [P], []),
    ("no path within five steps", CHAIN_BI + 6, [CHAIN_BI], []),
    ("exactly five steps, all against", CHAIN_BI + 5, [CHAIN_BI],
     [" <-knows,0> ".join(str(CHAIN_BI + 5 - i) for i in range(6))]),
    # nine least paths and a cap of seven: read from T backwards, the
    # smaller vertex before first, a step against its edge (-knows)
    # before one along it, a path before its extensions
    ("more paths than the cap: -t before +t", S, [T],
     [f"{S} <-knows,0> {M1} <-knows,0> {T}",
      f"{S} <knows,0> {M1} <-knows,0> {T}",
      f"{S} <-knows,0> {M1} <knows,0> {T}",
      f"{S} <knows,0> {M1} <knows,0> {T}",
      f"{S} <knows,0> {M2} <-knows,0> {T}",
      f"{S} <knows,0> {M2} <knows,0> {T}",
      f"{S} <-knows,0> {M3} <knows,0> {T}"]),
    ("several targets over the cap", S, [T, M1, Q], None)]


@pytest.mark.parametrize("what, a, targets, rows", BI_PAIRS,
                         ids=[p[0] for p in BI_PAIRS])
def test_bidirect_built_cases_agree(loaded_bi, what, a, targets, rows):
    _dep, client, graph, _t, _data = loaded_bi
    stmt = (f"FIND SHORTEST PATH FROM {a} TO "
            f"{', '.join(map(str, targets))} OVER knows BIDIRECT "
            f"UPTO 5 STEPS")
    want = _want(graph, a, targets, BI_SEM)
    if rows is not None:
        assert want == sorted((r,) for r in rows)
    else:
        assert len(want) == CAP
    assert _rows(client, stmt, "tpu") == want
    assert _rows(client, stmt, "cpu") == want


@pytest.mark.parametrize("block", range(4))
def test_bidirect_generated_pairs_agree(loaded_bi, block):
    dep, client, graph, _t, data = loaded_bi
    before = dep.rt.stats["path_device"]
    capped = reached = against = 0
    for a, b in _pairs(data, 80)[block * 20:(block + 1) * 20]:
        stmt = (f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows BIDIRECT "
                f"UPTO 5 STEPS")
        want = _want(graph, a, [b], BI_SEM)
        assert _rows(client, stmt, "tpu") == want, stmt
        assert _rows(client, stmt, "cpu") == want, stmt
        reached += bool(want)
        capped += len(want) == CAP
        against += sum(r[0].count("<-") for r in want)
    assert dep.rt.stats["path_device"] == before + 20
    # nearly every pair has an answer on the undirected reading, the
    # cap's order is exercised, and edges are crossed against
    assert reached >= 18 and capped >= 3 and against >= 20


@pytest.mark.parametrize("block", range(2))
def test_reversely_is_the_forward_walk_of_the_transposed_graph(
        loaded_bi, block):
    """PR 46's parent dropped the sign and walked REVERSELY forwards."""
    _dep, client, graph, transposed, data = loaded_bi
    differ = 0
    for a, b in _pairs(data, 40)[block * 20:(block + 1) * 20] \
            + [(T, S), (S, T), (Q, R), (CHAIN_BI + 5, CHAIN_BI)]:
        stmt = f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows REVERSELY"
        want = sorted((r[0].replace("<knows,", "<-knows,"),)
                      for r in _want(transposed, a, [b]))
        assert _rows(client, stmt, "tpu") == want, stmt
        assert _rows(client, stmt, "cpu") == want, stmt
        differ += [r[0].replace("<-", "<") for r in want] != \
            [r[0] for r in _want(graph, a, [b])]
    assert differ >= 10


def test_find_all_path_bidirect_agrees_between_backends(loaded_bi):
    """In no cell: FIND ALL PATH runs by the same code with the signed
    set, and the device path is held to the CPU walk."""
    _dep, client, _graph, _t, _data = loaded_bi
    stmt = f"FIND ALL PATH FROM {S} TO {T}, {M1} OVER knows BIDIRECT " \
           f"UPTO 3 STEPS"
    got = _rows(client, stmt, "tpu")
    assert got == _rows(client, stmt, "cpu") and len(got) == CAP
    stmt = f"FIND ALL PATH FROM {P} TO {R} OVER knows BIDIRECT UPTO 2 STEPS"
    got = _rows(client, stmt, "tpu")
    assert got == _rows(client, stmt, "cpu") == sorted(
        (r,) for r in BI_PAIRS[1][3])


def test_a_two_signed_statement_rides_the_two_signed_program(loaded_bi):
    """The dispatch record says both tables were read, the walk's span
    how many steps it crossed against their edge, and the predecessor
    order of the (-t, +t) set is one more index beside the forward
    one's, built once and clocked."""
    from benchmark.spans import walk
    from nebula_tpu.common import tracing
    from nebula_tpu.common.flight import recorder
    dep, client, _graph, _t, _data = loaded_bi
    rt = dep.rt
    tracing.trace_store.clear_for_tests()
    served, builds = rt.stats["path_device"], rt.stats["path_index_builds"]
    with flags_set({"trace_sample_rate": 1.0}):
        resp = client.execute(f"FIND SHORTEST PATH FROM {S} TO {T} OVER "
                              f"knows BIDIRECT UPTO 5 STEPS")
        forward = client.execute(
            f"FIND SHORTEST PATH FROM {S} TO {T} OVER knows")
    assert resp.ok() and len(resp.rows) == CAP and len(forward.rows) == 4
    assert rt.stats["path_device"] == served + 2
    nodes = [n for s in tracing.trace_store.summaries()
             for n in walk(tracing.trace_store.tree(int(s["id"], 16)))]
    walked = [n["tags"] for n in nodes
              if n["name"] == "tpu.path_reconstruct"]
    two, one = sorted(walked, key=lambda t: -t["rev_steps"])
    assert two["paths"] == CAP and two["capped"] and two["steps"] == 2 * CAP
    assert two["rev_steps"] == sum(r.count("<-") for r in BI_PAIRS[5][3])
    assert one["paths"] == 4 and one["rev_steps"] == 0 and one["steps"] == 8
    records = sorted((r for r in recorder.dump(limit=1 << 16)
                      if r.get("kernel") == "ell_bfs"),
                     key=lambda r: r["time_us"])[-2:]
    assert [(r["sides"], r["hop_onesided"] == r["levels"])
            for r in records] == [(2, False), (1, True)]
    assert records[0]["hop_onesided"] == 0 and records[0]["levels"] == 2
    mirror = rt.mirror(dep.cluster.graph_meta_client.get_space_id_by_name(
        dep.config["space"]).value())
    assert any(len(k) == 2 and k[0] == -k[1] for k in mirror._path_index)
    assert any(len(k) == 1 and k[0] > 0 for k in mirror._path_index)
    assert rt.stats["path_index_builds"] <= builds + 2
    assert rt.stats["path_index_us"] > 0


@pytest.mark.parametrize("seed", [46, 4_600_000_011])
def test_the_bidirect_reference_agrees_with_brute_force(seed):
    rng = np.random.default_rng(seed)
    n = 120
    key = np.unique(rng.integers(1, n + 1, 260) * (n + 1)
                    + rng.integers(1, n + 1, 260))
    edges = [(int(k // (n + 1)), int(k % (n + 1))) for k in key
             if k // (n + 1) != k % (n + 1)]
    src, dst = (np.asarray(c, np.int64) for c in zip(*edges))
    g = reference.Graph(src, dst, [{"w": 0}], np.zeros(len(src), np.int64))
    both = len(set(edges) & {(d, s) for s, d in edges})
    lengths, cut = set(), 0
    for _ in range(120):
        a, b = (int(x) for x in rng.integers(1, n + 1, 2))
        for cap in (1000, 3):
            want = _bipath_brute(edges, a, b, 4, cap)
            assert g.answer({**BI_SEM, "max_steps": 4, "max_paths": cap},
                            (a, b)) == want, (a, b, cap)
        lengths.add(want[0][0].count("<") if want else 0)
        cut += len(_bipath_brute(edges, a, b, 4, 1000)) > 3
    assert both >= 2 and {0, 2, 3, 4} <= lengths and cut >= 10


def test_forward_rows_are_what_the_parent_gave(loaded):
    """The forward statement's answers, order and cut on this graph,
    device path and CPU walk, hashed on PR 46's parent (567f070) by
    this loop: resolving the OVER set's signs moved no byte of them."""
    import hashlib
    _dep, client, _graph, data = loaded
    stmts = [f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows UPTO 5 STEPS"
             for a, b in _pairs(data, 80)]
    stmts += [f"FIND SHORTEST PATH FROM {A} TO {B}, {TOP + 30} OVER knows "
              f"UPTO 5 STEPS",
              f"FIND ALL PATH FROM {A} TO {B} OVER knows UPTO 3 STEPS",
              f"FIND SHORTEST PATH FROM {LONELY} TO {B} OVER * UPTO 4 STEPS"]
    h, n = hashlib.sha256(), 0
    for backend in ("tpu", "cpu"):
        for stmt in stmts:
            for row in _rows(client, stmt, backend):
                h.update(repr(row).encode())
                n += 1
            h.update(b"|")
    assert n == 508
    assert h.hexdigest() == ("f304eb7d190d73f6a31cc5517af7063f"
                             "365ced2606da3ba737e63f263a5f530e")
