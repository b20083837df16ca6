"""The Graph500 Kronecker graph of ``kronecker.py`` with a weight table
that can tell a float64 predicate from a float32 one.

Same structure, same draws and the same index per edge as
``kronecker.generate`` (it is called for them), so a configuration on
this generator shares its graph with one on that.  The weight is held
at ``weight_levels`` values k / weight_levels as there, except beside
each constant c of ``weight_split``: the level next below c becomes
c x (1 - 2^-30) and the level next above (c itself, where c is a level)
becomes c x (1 + 2^-30).  A double keeps the two apart (its spacing is
2^-53 of the value), float32 rounds both to float32(c) (its spacing is
2^-24), so ``w > c`` keeps the upper and drops the lower in float64 and
treats both alike in float32, whatever the rounding of the constant:
an evaluation below float64 answers one of the two wrong.  Which edges
a float64 predicate keeps is what it was on the even levels; two levels
in ``weight_levels`` move, each by less than one level's width.
"""
from __future__ import annotations

import math

import numpy as np

from benchmark.generators import kronecker

SPLIT = 2.0 ** -30


def split_levels(levels: int, constants) -> dict:
    """{level index: value} for the two levels beside each constant."""
    moved = {}
    for c in (float(c) for c in constants):
        above = math.ceil(c * levels)
        below = above - 1
        if below < 0 or above >= levels:
            raise ValueError(f"no level on both sides of {c} among "
                             f"{levels} levels")
        lo, hi = c * (1.0 - SPLIT), c * (1.0 + SPLIT)
        if not (below - 1) / levels < lo < c < hi < (above + 1) / levels:
            raise ValueError(f"{c}: the split levels leave their place")
        if not np.float32(lo) == np.float32(c) == np.float32(hi):
            raise ValueError(f"{c}: float32 tells the split levels apart")
        if below in moved or above in moved:
            raise ValueError(f"{c}: its levels are another constant's")
        moved[below], moved[above] = lo, hi
    return moved


def generate(params: dict, structure_seed: int) -> dict:
    out = kronecker.generate(params, structure_seed)
    prop = params["edge_prop"]
    for k, value in split_levels(int(params["weight_levels"]),
                                 params["weight_split"]).items():
        out["edge_prop_table"][k] = {prop: value}
    return out
