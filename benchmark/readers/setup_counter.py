"""A counter's growth during SET-UP: from the loaded deployment, before
the warm-up's first statement, to the window's start (``counters.start``
to ``counters.before``; ``counter_delta`` reads the window's own
growth).  What a program does once, at the first statement of a shape,
lies here: the clock of an index built in the warm-up says what that
build added to ``warmup_s``.  A counter the program does not publish
(this PR's parent), or one that stood still, reads as nothing.
select: {counter, scale}"""


def read(select: dict, record: dict):
    start, before = record["counters"]["start"], record["counters"]["before"]
    name = select["counter"]
    if name not in start or name not in before:
        return None
    grown = float(before[name]) - float(start[name])
    if not grown:
        return None
    return grown * float(select.get("scale", 1))
