"""``GO n STEPS FROM v OVER e WHERE e.p <op> c YIELD ...`` as the
configuration's ``guarantees`` state it: walk n-1 hops unfiltered,
keeping the SET of vertices reached at each hop, then return one row
for every out-edge of the last set whose property satisfies the
predicate — the predicate evaluated on the stored double in float64,
``>`` and ``<`` strict: a multiset, duplicates of a destination kept.
The filter meets the last hop's edges only; the hops before it follow
every edge.
semantics: {kind, steps, prop, op: ">" | ">=" | "<" | "<=", value,
            yield: ["_dst", ...], precision: "float64" (default)}

``precision: "float32"`` is the control and no cell's: the stored
double and the constant are both rounded to float32 before they are
compared, which is the nearest precision below the guarantee's.  On
the configuration's weight table (generators/kronecker_split.py) that
keeps other rows, and the harness says ``correct: false``.

Plain numpy over ``reference.Graph`` (``frontier``, the CSR row
pointer, the property table through ``eidx``); nothing of the program.
A window completes thousands of these and the last frontier of a
3-step statement has millions of out-edges, so the predicate is not
run edge by edge a statement: it is run once a graph over every edge
(``kept_edges``), and a statement takes its frontier's slices of the
edges it kept (10 ms where the edge-by-edge form took 0.1 s and the
comparison 8 minutes a run; ``benchmark/tests/test_go_where.py`` holds
it to that form)."""
import operator
from typing import Sequence, Tuple

import numpy as np

OPS = {">": operator.gt, ">=": operator.ge,
       "<": operator.lt, "<=": operator.le}


def kept_edges(graph, prop: str, op: str, value: float,
               precision: str = "float64"):
    """(before, dst): for the predicate ``prop <op> value`` run in
    ``precision`` over every edge in the graph's edge order (by source),
    ``before[e]`` counts the kept edges ahead of edge e (int64[m + 1])
    and ``dst`` holds the kept edges' destinations in that order; made
    once a graph and predicate and kept on it."""
    made = graph.__dict__.setdefault("_kept_edges", {})
    key = (prop, op, float(value), precision)
    if key not in made:
        dtype = {"float64": np.float64, "float32": np.float32}[precision]
        table = np.asarray([row[prop] for row in graph.etable],
                           np.float64).astype(dtype)
        keep = OPS[op](table[graph.eidx], dtype(value))
        before = np.zeros(len(keep) + 1, np.int64)
        np.cumsum(keep, out=before[1:])
        made[key] = (before, graph.dst[keep])
    return made[key]


def go_where(graph, start: int, steps: int, prop: str, op: str,
             value: float, yields: Sequence[str],
             precision: str = "float64") -> Tuple[np.ndarray, ...]:
    if list(yields) != ["_dst"]:
        raise ValueError(f"go_where yields _dst alone, not {yields!r}: a "
                         f"double column is not compared as an array")
    frontier = graph.frontier(start, steps - 1)
    before, dst = kept_edges(graph, prop, op, value, precision)
    # vertex v's out-edges are edges ptr[v]:ptr[v + 1]; the kept ones
    # among them are kept edges before[ptr[v]]:before[ptr[v + 1]]
    lo = before[graph.ptr[frontier]]
    n = before[graph.ptr[frontier + 1]] - lo
    total = int(n.sum())
    if total == 0:
        return (np.zeros(0, np.int64),)
    at = np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(total)
    return (dst[at],)


def answer(graph, semantics: dict, key: int):
    return go_where(graph, key, int(semantics["steps"]), semantics["prop"],
                    semantics["op"], float(semantics["value"]),
                    semantics["yield"],
                    semantics.get("precision", "float64"))
