"""Durations of one named span over the window's statements.
select: {span, requires_span?, reduce, scale}"""
from . import reduce_values, trees_with
from ..spans import walk


def read(select: dict, record: dict):
    vals = [n["duration_us"] for t in trees_with(
                record, select.get("requires_span"))
            for n in walk(t) if n["name"] == select["span"]]
    out = reduce_values(vals, select["reduce"])
    return None if out is None else out * float(select.get("scale", 1))
