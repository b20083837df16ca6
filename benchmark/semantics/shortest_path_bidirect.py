"""``FIND SHORTEST PATH FROM a TO b OVER e BIDIRECT UPTO n STEPS`` as
the configuration's ``guarantees`` state it: every path of the least
length from a to b on the UNDIRECTED graph, if that length is 1 to n,
one row a path; no row if there is none or a = b.  A step crosses a
stored edge of e from either end, and a path is a sequence of EDGES:
where u -> v and v -> u are both stored, the step between them is two
steps and gives two paths.  The text form names the direction each edge
was crossed in, ``u <e,0> v`` along a stored u -> v and ``u <-e,0> v``
against a stored v -> u (the signed type ``GO ... BIDIRECT`` yields as
``_type``).  Where there are more than ``max_paths`` such paths, the
first ``max_paths`` in this order: a path is read from its target
backwards, a step is (the vertex before, the signed edge type with -e
before +e, the rank, which is 0 here), the smaller step first, a path
before its extensions.
semantics: {kind, max_steps, max_paths, edge}

Plain numpy, nothing of the program.  The walk is the directed one over
the symmetrised edge list, every stored edge (s, d) written out as the
step s -> d along it and the step d -> s against it, each vertex's
steps INTO it kept by ascending (vertex before, sign): made once a
graph (one sort of 2 x the edges) and kept on it.  Crossing is
symmetric, so the same rows are what a vertex reaches in one step, and
the search goes level by level from both ends over them, each time from
the end whose frontier has fewer rows to read, so no pair sweeps the
table (the undirected walk covers this graph's giant component by its
third hop).  ``Graph.reading(undirected=True)`` holds the same rows by
degree and without the sign, which the order and the text need; it is
not made here a second time."""
import numpy as np

ARITY = 2


def steps_into(graph):
    """(ptr, before, along): the steps that end in vertex v are
    ``[ptr[v]:ptr[v + 1]]``, step i comes from ``before[i]`` and
    crosses its stored edge along it (``along[i]``: before -> v is
    stored) or against it (v -> before is stored), by ascending
    (before, against first); made once and kept on the graph."""
    made = graph.__dict__.get("_steps_into")
    if made is None:
        top = len(graph.deg)
        src = np.repeat(np.arange(top, dtype=np.int64), graph.deg)
        dst = graph.dst.astype(np.int64, copy=False)
        # the stored edge (s, d) ends in d along itself and in s
        # against itself
        end = np.concatenate((dst, src))
        before = np.concatenate((src, dst))
        along = np.zeros(len(end), bool)
        along[:len(dst)] = True
        order = np.argsort((end * top + before) * 2 + along)
        ptr = np.zeros(top + 1, np.int64)
        np.cumsum(np.bincount(end, minlength=top), out=ptr[1:])
        made = graph._steps_into = (ptr, before[order], along[order])
    return made


def neighbours(ptr, adj, frontier):
    """The entries of ``adj`` that the CSR rows of ``frontier`` hold."""
    n = ptr[frontier + 1] - ptr[frontier]
    total = int(n.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    return adj[np.repeat(ptr[frontier] - (np.cumsum(n) - n), n)
               + np.arange(total)]


def reached(ptr, adj, frontier):
    """The distinct vertices one step from the set ``frontier``,
    ascending: sorted out of a short list of entries, marked on the
    vertices out of a long one."""
    got = neighbours(ptr, adj, frontier)
    if len(got) * 16 < len(ptr):
        return np.unique(got)
    seen = np.zeros(len(ptr) - 1, bool)
    seen[got] = True
    return np.nonzero(seen)[0]


def steps_to_target(graph, a: int, b: int, max_steps: int):
    """(D, left): the least number D of steps from a to b, and for
    every vertex on a path of that length the steps it has left to b
    (-1 elsewhere); None where no path of 1 to ``max_steps`` steps
    exists."""
    ptr, before, _ = steps_into(graph)
    top = len(ptr) - 1
    if a == b or not 0 <= min(a, b) or max(a, b) >= top \
            or ptr[a + 1] == ptr[a] or ptr[b + 1] == ptr[b]:
        return None
    # the undirected graph is its own transpose: both ends search the
    # same rows, ``dist`` counts steps from the end's own vertex
    ends = [{"front": np.asarray([v]), "dist": np.full(top, -1, np.int8),
             "depth": 0} for v in (a, b)]
    for end, v in zip(ends, (a, b)):
        end["dist"][v] = 0
    while True:
        if ends[0]["depth"] + ends[1]["depth"] == max_steps:
            return None
        cost = [int((ptr[e["front"] + 1] - ptr[e["front"]]).sum())
                for e in ends]
        near, far = (ends[0], ends[1]) if cost[0] <= cost[1] \
            else (ends[1], ends[0])
        nxt = reached(ptr, before, near["front"])
        nxt = nxt[near["dist"][nxt] < 0]
        if not len(nxt):
            return None
        near["depth"] += 1
        near["dist"][nxt] = near["depth"]
        near["front"] = nxt
        # until now the two ends knew no vertex in common, so one they
        # both know now lies on a least path, at exactly the far end's
        # depth
        meet = nxt[far["dist"][nxt] >= 0]
        if len(meet):
            break
    from_a, to_b = ends
    D = from_a["depth"] + to_b["depth"]
    left = np.full(top, -1, np.int8)
    left[meet] = to_b["depth"]
    on = meet
    for i in range(to_b["depth"] - 1, -1, -1):          # towards b
        on = reached(ptr, before, on)
        on = on[to_b["dist"][on] == i]
        left[on] = i
    on = meet
    for j in range(from_a["depth"] - 1, -1, -1):        # towards a
        on = reached(ptr, before, on)
        on = on[from_a["dist"][on] == j]
        left[on] = D - j
    return D, left


def shortest_paths(graph, a: int, b: int, max_steps: int, max_paths: int,
                   edge: str):
    found = steps_to_target(graph, a, b, max_steps)
    if found is None:
        return []
    D, left = found
    ptr, before, along = steps_into(graph)
    crossed = {True: f" <{edge},0> ", False: f" <-{edge},0> "}
    rows, kept = [], {}

    def steps_kept(v: int, i: int) -> list:
        """The steps into v that come from a vertex one step farther
        from b, in their kept order; a vertex is i steps from b on
        every path through it, so its list is made once a statement."""
        if v not in kept:
            lo, hi = int(ptr[v]), int(ptr[v + 1])
            keep = left[before[lo:hi]] == i + 1
            kept[v] = list(zip(before[lo:hi][keep].tolist(),
                               along[lo:hi][keep].tolist()))
        return kept[v]

    def walk(v: int, i: int, tail: str) -> None:
        """Depth first from b, a vertex's steps in their kept order:
        paths come out in the stated order, so the first ``max_paths``
        are the answer.  Every stored edge is a step of its own."""
        if i == D:
            rows.append((f"{v}{tail}",))
            return
        for u, way in steps_kept(v, i):
            if len(rows) >= max_paths:
                return
            walk(u, i + 1, f"{crossed[way]}{v}{tail}")

    walk(b, 0, "")
    return sorted(rows)


def answer(graph, semantics: dict, key):
    a, b = key
    return shortest_paths(graph, int(a), int(b), int(semantics["max_steps"]),
                          int(semantics["max_paths"]), semantics["edge"])
