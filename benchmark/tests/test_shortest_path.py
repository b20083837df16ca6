"""``semantics/shortest_path.py`` against a brute-force enumeration,
and its control (run: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``).  CPU only, numpy only."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.semantics import shortest_path  # noqa: E402

SEM = {"kind": "shortest_path", "max_steps": 5, "max_paths": 1000,
       "edge": "knows"}


def _graph(edges) -> reference.Graph:
    src, dst = (np.asarray(c, np.int64) for c in zip(*edges))
    return reference.Graph(src, dst, [{"w": 0}], np.zeros(len(src),
                                                          np.int64))


def _random_graph(seed: int, n: int = 300, m: int = 1500):
    rng = np.random.default_rng(seed)
    key = np.unique(rng.integers(1, n + 1, m) * (n + 1)
                    + rng.integers(1, n + 1, m))
    edges = [(int(k // (n + 1)), int(k % (n + 1))) for k in key]
    return [e for e in edges if e[0] != e[1]]


def brute(edges, a: int, b: int, max_steps: int, max_paths: int):
    """Every walk of 1 to ``max_steps`` edges from a, by plain
    enumeration over an adjacency dict; the answer is the walks of the
    least length that end in b, cut by the stated order."""
    adj = {}
    for s, d in edges:
        adj.setdefault(s, []).append(d)
    level = [(a,)]
    for _ in range(max_steps):
        level = [p + (d,) for p in level for d in adj.get(p[-1], ())]
        hits = [p for p in level if p[-1] == b]
        if hits and a != b:
            hits.sort(key=lambda p: p[::-1])
            return sorted((" <knows,0> ".join(map(str, p)),)
                          for p in hits[:max_paths])
    return []


@pytest.mark.parametrize("seed", [3, 2_700_000_011, 3_999_999_937])
def test_random_graphs_agree_with_brute_force(seed):
    edges = _random_graph(seed)
    g = _graph(edges)
    rng = np.random.default_rng(seed)
    lengths = set()
    for _ in range(150):
        a, b = (int(x) for x in rng.integers(1, 301, 2))
        want = brute(edges, a, b, 4, 1000)
        got = g.answer({**SEM, "max_steps": 4}, (a, b))
        assert got == want, (a, b)
        lengths.add(want[0][0].count("<") if want else 0)
    assert {0, 2, 3} <= lengths      # unreachable in 4 and several depths


# a chain 1 -> 2 -> ... -> 8, a diamond ladder of 2 x 2 x 2 paths from
# 20 to 26, a vertex with no in-edge (40) and one with no out-edge (41)
BUILT = [(i, i + 1) for i in range(1, 8)] \
    + [(20, 21), (20, 22), (21, 23), (22, 23), (23, 24), (23, 25),
       (24, 26), (25, 26), (26, 27), (26, 28), (27, 29), (28, 29)] \
    + [(40, 1), (8, 41)]


@pytest.mark.parametrize("a, b, rows", [
    (1, 1, 0),          # a = b
    (1, 40, 0),         # the target has no in-edge
    (41, 1, 0),         # the start has no out-edge
    (8, 1, 0),          # unreachable
    (1, 6, 1),          # exactly max_steps away
    (1, 7, 0),          # one step beyond
    (20, 26, 4),        # two diamonds
    (20, 29, 0),        # three diamonds: 6 steps
    (23, 29, 4),
])
def test_built_cases(a, b, rows):
    got = _graph(BUILT).answer(SEM, (a, b))
    assert got == brute(BUILT, a, b, 5, 1000) and len(got) == rows


def _many_paths():
    """1 -> {10..19} -> {20..29} -> 2: 100 paths of 3 steps."""
    return [(1, m) for m in range(10, 20)] \
        + [(m, k) for m in range(10, 20) for k in range(20, 30)] \
        + [(k, 2) for k in range(20, 30)]


def test_more_paths_than_the_cap_are_cut_by_the_stated_order():
    edges = _many_paths()
    g = _graph(edges)
    assert len(g.answer(SEM, (1, 2))) == 100
    got = g.answer({**SEM, "max_paths": 25}, (1, 2))
    assert got == brute(edges, 1, 2, 5, 25) and len(got) == 25
    # read from the target backwards: the vertices before 2 are 20, 21
    # and half of 22's, each with its ten vertices before in order
    before = sorted((int(r[0].split(" <knows,0> ")[2]),
                     int(r[0].split(" <knows,0> ")[1])) for r in got)
    assert before == [(k, m) for k in (20, 21, 22)
                      for m in range(10, 20)][:25]


@pytest.mark.parametrize("weaken", ["drop", "duplicate", "outside_the_cap"])
def test_path_control_is_not_correct(weaken):
    """The reference put in the program's place with the guarantee
    broken: one path dropped, one duplicated, one replaced by a least
    path that is not among the first ``max_paths``."""
    g = _graph(_many_paths())
    want = g.answer({**SEM, "max_paths": 25}, (1, 2))
    again = want[::-1]                       # sound: another order
    assert reference.digest(again) == reference.digest(want)
    assert reference.same_rows(again, want)
    outside = [r for r in g.answer(SEM, (1, 2)) if r not in want]
    bad = {"drop": want[1:], "duplicate": want + want[:1],
           "outside_the_cap": want[1:] + outside[:1]}[weaken]
    assert len(outside) == 75
    assert reference.digest(bad) != reference.digest(want)
    assert not reference.same_rows(bad, want)


def test_in_edges_are_made_once_a_graph():
    g = _graph(BUILT)
    g.answer(SEM, (1, 6))
    made = shortest_path.in_edges(g)
    g.answer(SEM, (20, 26))
    assert shortest_path.in_edges(g) is made
