"""The BFS program's share of its bytes-bound roofline over the traced
interval: the levels that the program's dispatch records of the
interval say their loops ran, times the bytes one level has to move at
the record's lane rung (bfs_bytes.level_bytes, from the loaded table
shapes), over the device time the trace shows for the program in the
same interval, against the chip's published HBM rate.  A record is
written when its dispatch ends, so a program that runs across an edge
of the interval counts all of its levels or none against the part of
its time inside: with ten dispatches in the interval the share swings
by about a tenth either way.  A record without the level count (a
program from before it reported one) reads as nothing.
select: {program: regex, kind, kernel, levels, lanes}"""
from ..bfs_bytes import level_bytes
from .trace_program import matched


def read(select: dict, record: dict):
    got, interval = matched(select, record), record.get("traced_us")
    if got is None or interval is None or not record.get("peaks"):
        return None     # a CPU rehearsal has no peak to hold it against
    seconds, _runs = got
    facts = record["facts"]
    rows = [r for r in record["flight"]
            if r.get("kind") == select["kind"]
            and r.get("kernel") == select["kernel"]
            and interval[0] <= r.get("time_us", 0) <= interval[1]]
    if not rows or not seconds \
            or any(select["levels"] not in r for r in rows):
        return None
    moved = sum(r[select["levels"]] * level_bytes(
        facts["ell_shapes"], facts["ell_index_itemsize"],
        facts["ell_etype_itemsize"], int(r[select["lanes"]]))
        for r in rows)
    if not moved:
        return None
    return 100.0 * moved / record["peaks"]["hbm_bytes_per_s"] / seconds
