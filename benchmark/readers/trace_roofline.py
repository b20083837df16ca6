"""A hop program's share of its bytes-bound roofline: the least bytes a
hop must move (bytes_model.hop_bytes, from the loaded table shapes and
the lane width the program's own kernel span states) over the device
time the trace shows for it, against the chip's published HBM rate.
select: {program: regex, width_span, width_kind, width_tag}"""
from ..bytes_model import hop_bytes
from ..spans import walk
from .trace_program import matched


def read(select: dict, record: dict):
    got = matched(select, record)
    if got is None:
        return None
    seconds, runs = got
    lanes = {n["tags"].get(select["width_tag"])
             for t in record["trees"] for n in walk(t)
             if n["name"] == select["width_span"]
             and n["tags"].get("kind") == select["width_kind"]}
    lanes.discard(None)
    if not lanes or not seconds:
        return None
    facts = record["facts"]
    least = hop_bytes(facts["ell_shapes"], facts["ell_index_itemsize"],
                      facts["ell_etype_itemsize"], min(lanes) // 8)
    return 100.0 * least * runs / record["peaks"]["hbm_bytes_per_s"] \
        / seconds
