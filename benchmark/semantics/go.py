"""``GO n STEPS FROM v OVER e YIELD ...`` (nebula's semantics, stated
by the configurations' ``guarantees``): walk n-1 hops keeping the SET
of vertices reached at each hop, then return one row per out-edge of
that set — a multiset, duplicates of a destination kept.
semantics: {kind, steps, yield: ["_dst" | <edge property>, ...]}"""
from typing import Sequence, Tuple

import numpy as np


def go(graph, start: int, steps: int, yields: Sequence[str]
       ) -> Tuple[np.ndarray, ...]:
    pos = graph.edge_positions(graph.frontier(start, steps - 1))
    cols = []
    for y in yields:
        if y == "_dst":
            cols.append(graph.dst[pos])
        else:
            table = np.asarray([row[y] for row in graph.etable],
                               np.int64)
            cols.append(table[graph.eidx[pos]])
    return tuple(cols)


def answer(graph, semantics: dict, key: int):
    return go(graph, key, int(semantics["steps"]), semantics["yield"])
