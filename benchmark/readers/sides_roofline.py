"""A hop program's share of its bytes-bound roofline over the traced
interval: what the hops of the tick records that fall in the interval
had to move (bytes_model.visited_bytes: from the slots each record
says its hops visited, how many direction tables they read (2 where
the record's one-sided hops are 0, a two-signed OVER set, ``GO ...
BIDIRECT``; else 1), the loaded table shapes and the lane width the
program's own kernel span states) over the device time the trace shows
for the program in the same interval, against the chip's published HBM
rate.  A hop's report is read a tick after the hop ran, so the
interval's edges may hold or miss one hop's slots; hops whose report
was never read count no bytes, so the share errs low, not high.  On a
record without its hop fields (a program from before them) it reads
nothing.
select: {program: regex, width_span, width_kind, width_tag,
         kind, hops, pushes, slots, onesided}"""
from ..bytes_model import visited_bytes
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
