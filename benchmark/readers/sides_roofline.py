"""A hop program's share of its bytes-bound roofline over the traced
interval where a hop may read both direction tables (a two-signed OVER
set, ``GO ... BIDIRECT``): slots_roofline's arithmetic with the bytes
of sides_bytes.visited_bytes, which takes from each tick record how
many tables its hops read (2 where the record's one-sided hops are 0,
else 1) and charges a pull every table it swept at a pull's rate and
the carriers once.  On a one-sided record it is slots_roofline's
number; on a record without its hop fields (a program from before
them) it reads nothing.  The rest as there: the program's device time
in the traced interval, the chip's published HBM rate, the lane width
the program's own kernel span states.
select: {program: regex, width_span, width_kind, width_tag,
         kind, hops, pushes, slots, onesided}"""
from ..sides_bytes import visited_bytes
from ..spans import walk
from .trace_program import matched


def read(select: dict, record: dict):
    got, interval = matched(select, record), record.get("traced_us")
    if got is None or interval is None or not record.get("peaks"):
        return None     # a CPU rehearsal has no peak to hold it against
    seconds, _runs = got
    lanes = {n["tags"].get(select["width_tag"])
             for t in record["trees"] for n in walk(t)
             if n["name"] == select["width_span"]
             and n["tags"].get("kind") == select["width_kind"]}
    lanes.discard(None)
    if not lanes or not seconds:
        return None
    facts = record["facts"]
    moved = [
        visited_bytes(r.get(select["hops"]), r.get(select["pushes"]),
                      r.get(select["slots"]), r.get(select["onesided"]),
                      facts["ell_shapes"], facts["ell_index_itemsize"],
                      facts["ell_etype_itemsize"], min(lanes) // 8)
        for r in record["flight"] if r.get("kind") == select["kind"]
        and interval[0] <= r.get("time_us", 0) <= interval[1]]
    if None in moved or not sum(moved):
        return None     # a record's slots belie the tables': no share
    return 100.0 * sum(moved) / record["peaks"]["hbm_bytes_per_s"] / seconds
