"""The BFS program's share of its bytes-bound roofline over the traced
interval, counting the tables a level read: what the program's dispatch
records of the interval say their levels had to move
(bfs_sides_bytes.dispatch_bytes: the pulled levels, ``levels`` less
``levels_push``, each a sweep of ``sides`` tables; the pushed levels
the slots they visited; every level one pass over the rows' carriers
and depths; at the record's lane rung, from the loaded table shapes)
over the device time the trace shows for the program in the same
interval, against the chip's published HBM rate.  A record is written
when its dispatch ends, so a program that runs across an edge of the
interval counts all of its levels or none against the part of its time
inside (readers/levels_roofline.py says by how much that swings).  A
record without ``sides`` (a program from before the field) reads as
nothing, and so does one whose slots belie the tables'.
select: {program: regex, kind, kernel, levels, pushed, slots, sides,
         lanes}"""
from ..bfs_sides_bytes import dispatch_bytes
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
    if not rows or not seconds:
        return None
    moved = [dispatch_bytes(
        r.get(select["levels"]), r.get(select["pushed"]),
        r.get(select["slots"]), r.get(select["sides"]),
        facts["ell_shapes"], facts["ell_index_itemsize"],
        facts["ell_etype_itemsize"], int(r[select["lanes"]]))
        for r in rows]
    if None in moved or not sum(moved):
        return None
    return 100.0 * sum(moved) / record["peaks"]["hbm_bytes_per_s"] / seconds
