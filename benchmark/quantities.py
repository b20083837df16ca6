"""The end-to-end quantities the harness takes itself.  An end-to-end
metric is a file ``end_metrics/<name>.json`` that names one of these
and its parameters; a new quantity is new code here, which only a
``benchmark`` PR may add.

``window`` is what run.py hands over: ``records`` (one per statement
sent, with ``due``/``done`` on the perf_counter clock, ``traversal``,
``failed``), ``seconds``, ``t_end``, ``deadline_s``, ``start_to_window_s``,
``peak_bytes``, ``edges``.  A failed statement (refused, wrong, late)
counts in no rate and enters a latency sample at the deadline.
"""
from __future__ import annotations

from typing import Optional

from .readers import reduce_values


def latency(spec: dict, window: dict) -> Optional[float]:
    vals = [max(r["done"] - r["due"],
                window["deadline_s"] if r["failed"] else 0.0)
            for r in window["records"]
            if spec.get("of") != "traversal" or r["traversal"]]
    out = reduce_values(vals, spec["reduce"])
    return None if out is None else out * float(spec.get("scale", 1))


def completed_per_second(spec: dict, window: dict) -> float:
    return sum(1 for r in window["records"] if not r["failed"]
               and r["done"] <= window["t_end"]) / window["seconds"]


def peak_bytes_per_edge(spec: dict, window: dict) -> float:
    return window["peak_bytes"] / window["edges"]


def setup_seconds(spec: dict, window: dict) -> float:
    return window["start_to_window_s"]


QUANTITIES = {f.__name__: f for f in (latency, completed_per_second,
                                      peak_bytes_per_edge, setup_seconds)}
