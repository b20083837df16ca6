"""``FIND SHORTEST PATH FROM a TO b OVER e UPTO n STEPS`` as the
configuration's ``guarantees`` state it: every path of the least
length from a to b along out-edges, if that length is 1 to n, one row
a path in nebula's text form (``a <e,0> x <e,0> b``); no row if there
is none or a = b.  Where there are more than ``max_paths`` such paths,
the first ``max_paths`` in this order: a path is read from its target
backwards (b, the vertex before b, ..., a) and two paths compare by
those id sequences, the smaller id first.
semantics: {kind, max_steps, max_paths, edge}

Plain numpy, nothing of the program.  The search goes level by level
from both ends, each time from the end whose frontier has fewer edges
to follow, so no pair sweeps the edge table (a forward search alone
took 0.57 s a pair at 16.1 M edges, ISSUE 27); the in-edge lists are
made once a graph and kept on it."""
import numpy as np

ARITY = 2


def in_edges(graph):
    """(ptr, src): the sources of vertex v's in-edges, ascending, at
    ``src[ptr[v]:ptr[v + 1]]``; made once and kept on the graph."""
    made = graph.__dict__.get("_in_edges")
    if made is None:
        top = len(graph.deg)
        src = np.repeat(np.arange(top, dtype=np.int64), graph.deg)
        ptr = np.zeros(top + 1, np.int64)
        np.cumsum(np.bincount(graph.dst, minlength=top), out=ptr[1:])
        # graph.dst lies by ascending source: a stable sort by
        # destination keeps the sources of one destination ascending
        made = graph._in_edges = (
            ptr, src[np.argsort(graph.dst, kind="stable")])
    return made


def neighbours(ptr, adj, frontier):
    """The entries of ``adj`` that the CSR rows of ``frontier`` hold."""
    n = ptr[frontier + 1] - ptr[frontier]
    total = int(n.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    return adj[np.repeat(ptr[frontier] - (np.cumsum(n) - n), n)
               + np.arange(total)]


def steps_to_target(graph, a: int, b: int, max_steps: int):
    """(D, step): the least length D of a path a -> b, and for every
    vertex on such a path its number of steps to b (-1 elsewhere); None
    where there is no path of 1 to ``max_steps`` steps."""
    in_ptr, in_src = in_edges(graph)
    top = len(graph.deg)
    if a == b or max(a, b) >= top or in_ptr[b + 1] == in_ptr[b] \
            or graph.deg[a] == 0:
        return None
    # out: distance from a, followed along out-edges; back: distance to
    # b, followed along in-edges
    out = {"ptr": graph.ptr, "adj": graph.dst, "front": np.asarray([a]),
           "dist": np.full(top, -1, np.int8), "depth": 0}
    back = {"ptr": in_ptr, "adj": in_src, "front": np.asarray([b]),
            "dist": np.full(top, -1, np.int8), "depth": 0}
    out["dist"][a] = back["dist"][b] = 0
    while True:
        if out["depth"] + back["depth"] == max_steps:
            return None
        cost = [int((s["ptr"][s["front"] + 1] - s["ptr"][s["front"]]).sum())
                for s in (out, back)]
        side, other = (out, back) if cost[0] <= cost[1] else (back, out)
        nxt = np.unique(neighbours(side["ptr"], side["adj"], side["front"]))
        nxt = nxt[side["dist"][nxt] < 0]
        if not len(nxt):
            return None
        side["depth"] += 1
        side["dist"][nxt] = side["depth"]
        side["front"] = nxt
        # no path of out.depth + back.depth - 1 steps exists, so a
        # vertex both sides know lies on a least path, at exactly the
        # other side's depth
        meet = nxt[other["dist"][nxt] >= 0]
        if len(meet):
            break
    D = out["depth"] + back["depth"]
    step = np.full(top, -1, np.int8)
    step[meet] = back["depth"]
    on = meet
    for i in range(back["depth"] - 1, -1, -1):      # towards b
        on = np.unique(neighbours(graph.ptr, graph.dst, on))
        on = on[back["dist"][on] == i]
        step[on] = i
    on = meet
    for j in range(out["depth"] - 1, -1, -1):       # towards a
        on = np.unique(neighbours(in_ptr, in_src, on))
        on = on[out["dist"][on] == j]
        step[on] = D - j
    return D, step


def shortest_paths(graph, a: int, b: int, max_steps: int, max_paths: int,
                   edge: str):
    found = steps_to_target(graph, a, b, max_steps)
    if found is None:
        return []
    D, step = found
    in_ptr, in_src = in_edges(graph)
    rows = []

    def walk(v: int, i: int, tail: str) -> None:
        """Depth first from b, a vertex's in-edges by ascending source:
        paths come out in the stated order, so the first ``max_paths``
        are the answer."""
        if i == D:
            rows.append((f"{v}{tail}",))
            return
        before = in_src[in_ptr[v]:in_ptr[v + 1]]
        for u in before[step[before] == i + 1].tolist():
            if len(rows) >= max_paths:
                return
            walk(u, i + 1, f" <{edge},0> {v}{tail}")

    walk(b, 0, "")
    return sorted(rows)


def answer(graph, semantics: dict, key):
    a, b = key
    return shortest_paths(graph, int(a), int(b), int(semantics["max_steps"]),
                          int(semantics["max_paths"]), semantics["edge"])
