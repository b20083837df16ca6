"""``GO k STEPS FROM v OVER e YIELD DISTINCT e._dst``: the k-hop
neighbourhood itself.  One row for each distinct vertex that ends a
walk of exactly k edges from v, each once (a vertex a shorter walk also
reaches is in it, v is if a k-walk returns to it), and no row where
there is none.  The distinct destinations of the k-th hop are the k-th
frontier, so the answer is that frontier as one int64 column; a
statement without ORDER BY promises no order, and the comparison asks
for none.
semantics: {kind, steps}

A hop is ``reference.Graph.frontier``'s, one set to the next: mark the
destinations of the set's out-edges.  It is walked here a vertex at a
time over the CSR's own rows (a contiguous slice of ``dst`` each) and
not through ``Graph.edge_positions``, which builds an index for every
edge of the set: a 3-step statement's last hop leaves from 18 k
vertices that hold 4.73 M edges, a window completes some 1,500 of them,
and their reference took 200 s of a run through the index and takes a
fifth of that this way (PERF.md section 6, PR 38).  ``go_count_distinct``'s
complement side would never be taken at k = 2 and 3 (the set a hop
leaves from holds under a third of the edges) and is not copied.
tests/test_neigh.py holds this walk to ``Graph.frontier`` and to one
that uses neither."""
import numpy as np


def hop(graph, frontier: np.ndarray) -> np.ndarray:
    """The set one hop on from the set ``frontier`` (ascending labels)."""
    seen = np.zeros(len(graph.deg), bool)
    for lo, hi in zip(graph.ptr[frontier].tolist(),
                      graph.ptr[frontier + 1].tolist()):
        seen[graph.dst[lo:hi]] = True
    return np.nonzero(seen)[0]


def neighbourhood(graph, start: int, steps: int) -> np.ndarray:
    frontier = np.asarray([start], np.int64)
    for _ in range(steps):
        frontier = hop(graph, frontier)
    return frontier.astype(np.int64, copy=False)


def answer(graph, semantics: dict, key: int):
    return (neighbourhood(graph, key, int(semantics["steps"])),)
