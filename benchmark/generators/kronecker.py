"""Graph500 Kronecker (R-MAT) edge generator.

The Graph500 specification's generator: 2^scale vertices, edgefactor x
2^scale generated edges, each placed by descending `scale` levels of
the 2x2 initiator (A, B, C, D).  The specification permutes vertex
labels afterwards; the harness does that from ``--seed`` (run.py), so
this returns STRUCTURAL ids and the same structure for every seed.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, structure_seed: int) -> dict:
    scale, ef = int(params["scale"]), int(params["edgefactor"])
    a, b, c = (float(params[k]) for k in ("A", "B", "C"))
    n, m = 1 << scale, ef << scale
    rng = np.random.default_rng(structure_seed)
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    ab, abc = np.float32(a + b), np.float32(a + b + c)
    for bit in range(scale):        # one draw places one level's quadrant
        r = rng.random(m, dtype=np.float32)
        src_bit = r >= ab
        dst_bit = ((r >= np.float32(a)) & ~src_bit) | (r >= abc)
        src |= src_bit.astype(np.int32) << bit
        dst |= dst_bit.astype(np.int32) << bit
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    # the specification's edge weight, uniform in [0, 1), held at
    # ``weight_levels`` evenly spaced values: the loader takes a table
    # of distinct property rows and an index per edge
    k = int(params["weight_levels"])
    return {"n_vertices": n, "src": src, "dst": dst, "generated_edges": m,
            "edge_prop_table": [{params["edge_prop"]: i / k}
                                for i in range(k)],
            "edge_prop_idx": rng.integers(0, k, m, dtype=np.int64)}
