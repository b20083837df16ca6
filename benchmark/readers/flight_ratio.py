"""The sum of one field over the sum of another, over the flight
recorder's records of the window that carry both.
select: {kind, top, bottom, scale}"""


def read(select: dict, record: dict):
    rows = [r for r in record["flight"] if r.get("kind") == select["kind"]
            and select["top"] in r and select["bottom"] in r]
    bottom = sum(r[select["bottom"]] for r in rows)
    if not bottom:
        return None
    return sum(r[select["top"]] for r in rows) / bottom \
        * float(select.get("scale", 1))
