"""Device time of the programs whose name matches a pattern, per run of
the program, from the reduced device trace.
select: {program: regex, scale}"""
import re


def matched(select: dict, record: dict):
    trace = record.get("trace")
    if not trace:
        return None
    pat = re.compile(select["program"])
    names = [n for n in trace["program_s"] if pat.search(n)]
    runs = sum(trace["program_runs"][n] for n in names)
    if not runs:
        return None
    return sum(trace["program_s"][n] for n in names), runs


def read(select: dict, record: dict):
    got = matched(select, record)
    if got is None:
        return None
    seconds, runs = got
    return seconds / runs * float(select.get("scale", 1))
