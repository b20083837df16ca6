"""``GO k STEPS FROM v OVER e BIDIRECT YIELD DISTINCT e._dst | YIELD
COUNT(*)``: the k-hop neighbourhood count of the UNDIRECTED graph.  A
step crosses an edge of e from either end, so the walk is the directed
one over the symmetrised edge list: every stored edge (s, d) read as
s -> d and as d -> s.  One row, the number of distinct vertices that
end a walk of exactly k such steps from v (a vertex a shorter walk also
reaches counts; v counts whenever k >= 2 and v has an edge at all: out
and back), and no row where there is none (a vertex with no edge at
either end).  A pair stored in both orders is two stored edges and one
neighbour.  The distinct ends of the k-th step are the k-th frontier,
so the number is ``len(frontier_k)``.
semantics: {kind, steps}

The symmetrised graph is built once a graph from ``Graph.ptr`` /
``Graph.dst`` (the source of edge i is the row that holds i), as a CSR
of its own: rows by vertex label, 2 x the edges, a row's neighbours in
order of their degree, the largest first.  A hop is taken from
whichever side is the cheaper, as ``go_count_distinct.py``'s is.  Out
of a set that holds few of the edge ends it is pushed: mark the
neighbours of its members.  Out of a set that holds a tenth of them or
more (here the third to sixth hops: the undirected walk covers the
giant component a hop sooner than the directed one) it is pulled, the
bottom-up step of a direction-optimising search (Beamer, Asanovic,
Patterson, SC 2012; the Graph500 reference code's own): an undirected
v is reached when ANY of its neighbours lies in the set, so every
vertex asks its first neighbour, the one of the largest degree and the
likeliest member, and only the vertices that missed ask the rest of
theirs.  The directed file's complement side reads every edge end of
the vertices OUTSIDE the set, which at the third hop is most of them:
0.14 s a statement at scale 20 where this reads 0.02, and a window
holds some 1,300 (a run has to end inside the driver's 360 s).
tests/test_bidir.py holds both sides to a walk that does neither, a
vertex at a time."""
import numpy as np

_HELD = []          # [graph, ptr, nbr, deg, have, first]: one graph a run
PULL_FROM = 0.1     # of the edge ends, inside the set


def symmetrised(graph):
    """(ptr, nbr, deg) of the undirected reading: ``nbr[ptr[v]:
    ptr[v + 1]]`` holds the far end of every stored edge that has v at
    either end (a pair stored both ways is there twice), the far ends
    of the largest degree first."""
    if not _HELD or _HELD[0] is not graph:
        n = len(graph.deg)
        src = np.repeat(np.arange(n, dtype=np.int64), graph.deg)
        dst = graph.dst.astype(np.int64)
        ends = np.concatenate((src, dst))
        far = np.concatenate((dst, src))
        deg = np.bincount(ends, minlength=n)
        rank = np.empty(n, np.int64)        # 0 for the largest degree
        rank[np.argsort(-deg, kind="stable")] = np.arange(n)
        nbr = far[np.argsort(ends * n + rank[far])]
        ptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=ptr[1:])
        have = np.nonzero(deg)[0]
        _HELD[:] = [graph, ptr, nbr, deg, have, nbr[ptr[have]]]
    return _HELD[1], _HELD[2], _HELD[3]


def _positions(ptr, deg, members: np.ndarray, skip: int = 0) -> tuple:
    """The places in ``nbr`` of every edge end of ``members`` but
    their first ``skip``, and how many each member has there."""
    n = deg[members] - skip
    total = int(n.sum())
    if total == 0:
        return np.zeros(0, np.int64), n
    starts = np.repeat(ptr[members] + skip
                       - np.concatenate(([0], np.cumsum(n)[:-1])), n)
    return starts + np.arange(total), n


def hop(graph, frontier: np.ndarray, pull=None) -> np.ndarray:
    """The set one undirected hop on from the set ``frontier``
    (ascending labels).  ``pull`` forces a side (a test's); None takes
    the cheaper."""
    ptr, nbr, deg = symmetrised(graph)
    if pull is None:
        pull = int(deg[frontier].sum()) > PULL_FROM * len(nbr)
    seen = np.zeros(len(deg), bool)
    if not pull:
        seen[nbr[_positions(ptr, deg, frontier)[0]]] = True
        return np.nonzero(seen)[0]
    have, first = _HELD[4], _HELD[5]
    inside = np.zeros(len(deg), bool)
    inside[frontier] = True
    hit = inside[first]
    seen[have[hit]] = True
    missed = have[~hit]
    rest, n = _positions(ptr, deg, missed, skip=1)
    seen[np.repeat(missed, n)[inside[nbr[rest]]]] = True
    return np.nonzero(seen)[0]


def khop_count(graph, start: int, steps: int, pull=None) -> int:
    frontier = np.asarray([start], np.int64)
    for _ in range(steps):
        frontier = hop(graph, frontier, pull)
    return len(frontier)


def answer(graph, semantics: dict, key: int):
    n = khop_count(graph, key, int(semantics["steps"]))
    return [(n,)] if n else []
