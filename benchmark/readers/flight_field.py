"""One field of the flight recorder's records of the window.
select: {kind, field, reduce, scale}"""
from . import reduce_values


def read(select: dict, record: dict):
    vals = [r[select["field"]] for r in record["flight"]
            if r.get("kind") == select["kind"] and select["field"] in r]
    out = reduce_values(vals, select["reduce"])
    return None if out is None else out * float(select.get("scale", 1))
