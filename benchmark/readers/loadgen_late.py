"""How late the open-loop generator sent: send time - due time.
select: {reduce, scale}"""
from . import reduce_values


def read(select: dict, record: dict):
    out = reduce_values(record["late_s"], select["reduce"])
    return None if out is None else out * float(select.get("scale", 1))
