"""The per-lane count program's share of its bytes-bound roofline over
the traced interval: the runs of the program the device trace shows in
the interval, times the bytes one count has to move at the lane width
the program's own kernel span states (count_bytes.count_bytes, from
the loaded table shapes), over the device time the trace shows for the
program in the same interval, against the chip's published HBM rate.
A run that spans an edge of the interval counts whole against the part
of its time inside: with forty runs in the interval the share errs
high by a fortieth at most.  A program without the count (the parent)
has no such run in its trace and reads as nothing.
select: {program: regex, width_span, width_kind, width_tag}"""
from ..count_bytes import count_bytes
from ..spans import walk
from .trace_program import matched


def read(select: dict, record: dict):
    got = matched(select, record)
    if got is None or not record.get("peaks"):
        return None     # a CPU rehearsal has no peak to hold it against
    seconds, runs = got
    lanes = {n["tags"].get(select["width_tag"])
             for t in record["trees"] for n in walk(t)
             if n["name"] == select["width_span"]
             and n["tags"].get("kind") == select["width_kind"]}
    lanes.discard(None)
    if not lanes or not seconds:
        return None
    facts = record["facts"]
    moved = runs * count_bytes(facts["ell_shapes"], facts["ell_hub_rows"],
                               int(min(lanes)))
    return 100.0 * moved / record["peaks"]["hbm_bytes_per_s"] / seconds
