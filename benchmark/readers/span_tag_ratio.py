"""The sum of one numeric tag over the sum of another, over the spans
of one name in the window's statements that carry both: a cost for
each unit of work (thread time for each candidate edge), which does
not move with how small and large spans mix as a mean over spans does.
select: {span, top, bottom, requires_span?, scale}"""
from . import trees_with
from ..spans import walk


def read(select: dict, record: dict):
    top = bottom = 0
    for t in trees_with(record, select.get("requires_span")):
        for n in walk(t):
            tags = n.get("tags", {})
            if n["name"] == select["span"] and select["top"] in tags \
                    and select["bottom"] in tags:
                top += tags[select["top"]]
                bottom += tags[select["bottom"]]
    if not bottom:
        return None
    return top / bottom * float(select.get("scale", 1))
