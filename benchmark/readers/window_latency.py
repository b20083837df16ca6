"""A statistic of the window's statement latencies, due to done, as
the harness's own clock has them in THIS run (a traced run's read
1.3-2x an untraced run's): the end-to-end quantity ``latency`` of
quantities.py, read as a per-layer metric.
select: {reduce, scale, of}"""
from ..quantities import latency


def read(select: dict, record: dict):
    return latency(select, record["window"])
