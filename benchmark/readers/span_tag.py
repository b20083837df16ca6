"""One numeric tag of one named span over the window's statements; a
span without the tag reads as nothing.
select: {span, tag, requires_span?, reduce, scale}"""
from . import reduce_values, trees_with
from ..spans import walk


def read(select: dict, record: dict):
    vals = [n["tags"][select["tag"]] for t in trees_with(
                record, select.get("requires_span"))
            for n in walk(t) if n["name"] == select["span"]
            and select["tag"] in n.get("tags", {})]
    out = reduce_values(vals, select["reduce"])
    return None if out is None else out * float(select.get("scale", 1))
