"""``GO k STEPS FROM v OVER e YIELD DISTINCT e._dst | YIELD COUNT(*)``:
the k-hop neighbourhood count.  One row, the number of distinct
vertices that end a walk of exactly k edges from v (a vertex a shorter
walk also reaches counts, v counts if a k-walk returns to it), and no
row where there is none (a pipe with no input yields nothing, on both
of the program's backends).  The distinct destinations of the k-th hop
are the k-th frontier, so the number is ``len(frontier_k)``.
semantics: {kind, steps}

A hop is ``reference.Graph.frontier``'s, one set to the next, taken
from whichever side is the smaller.  Out of a set F that holds few of
the edges it is that function's own walk: mark the destinations of F's
out-edges.  Out of a set that holds most of them (the fourth to sixth
hops on a Kronecker graph: 0.5 M of the 0.55 M vertices that have an
out-edge) it is the same set by its complement: v is reached unless
every one of its in-edges starts outside F, so count the in-edges that
do (the out-edges of the few sources outside F) and keep the vertices
with more in-edges than that.  The first costs F's edges, the second
the others': a 6-step statement at scale 20 is 0.7-1.6 s of numpy the
first way alone (all 16 M edges, three times over), some 0.1 s this
way, and a window holds hundreds.  tests/test_khop.py holds both ways
to a walk that does neither."""
import numpy as np

_HELD = []          # [graph, its in-degree per vertex label]: one graph a run


def _in_degree(graph) -> np.ndarray:
    if not _HELD or _HELD[0] is not graph:
        _HELD[:] = [graph, np.bincount(graph.dst,
                                       minlength=len(graph.deg))]
    return _HELD[1]


def hop(graph, frontier: np.ndarray, complement=None) -> np.ndarray:
    """The set one hop on from the set ``frontier`` (ascending labels).
    ``complement`` forces a side (a test's); None takes the cheaper."""
    inside = int(graph.deg[frontier].sum())
    if complement is None:
        complement = 2 * inside > len(graph.dst)
    if not complement:
        seen = np.zeros(len(graph.deg), bool)
        seen[graph.dst[graph.edge_positions(frontier)]] = True
        return np.nonzero(seen)[0]
    outside = graph.deg > 0
    outside[frontier] = False
    missed = np.bincount(
        graph.dst[graph.edge_positions(np.nonzero(outside)[0])],
        minlength=len(graph.deg))
    return np.nonzero(_in_degree(graph) > missed)[0]


def khop_count(graph, start: int, steps: int, complement=None) -> int:
    frontier = np.asarray([start], np.int64)
    for _ in range(steps):
        frontier = hop(graph, frontier, complement)
    return len(frontier)


def answer(graph, semantics: dict, key: int):
    n = khop_count(graph, key, int(semantics["steps"]))
    return [(n,)] if n else []
