"""A counter's growth over the window, optionally per unit of another.
select: {counter, per?: counter name | "statements", scale}
A counter the program does not publish reads as nothing."""


def read(select: dict, record: dict):
    before, after = record["counters"]["before"], record["counters"]["after"]

    def delta(name):
        if name == "statements":
            return float(record["statements_done"])
        if name not in after or name not in before:
            return None
        return float(after[name]) - float(before[name])

    top = delta(select["counter"])
    if top is None:
        return None
    if select.get("per"):
        per = delta(select["per"])
        if not per:
            return None
        top /= per
    return top * float(select.get("scale", 1))
