"""A set-up stage clock of the harness.  select: {stages: [...]}"""


def read(select: dict, record: dict):
    if any(s not in record["stages"] for s in select["stages"]):
        return None
    return float(sum(record["stages"][s] for s in select["stages"]))
