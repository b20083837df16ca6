"""Per-statement self time of some critical-path phases (spans.py).
select: {phases: [...], requires_span?, reduce, scale}"""
from . import reduce_values, trees_with
from ..spans import phases


def read(select: dict, record: dict):
    vals = []
    for tree in trees_with(record, select.get("requires_span")):
        p = phases(tree)
        if p is not None:
            vals.append(sum(p[name] for name in select["phases"]))
    out = reduce_values(vals, select["reduce"])
    return None if out is None else out * float(select.get("scale", 1))
